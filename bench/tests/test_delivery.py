"""Token delivery times rebuilt by the load client from a scripted session that
behaves like the async loop: a request admitted in one step delivers its
first token with its second, one step later, and then one per step."""
import types

import numpy as np
import pytest

import run


class Slot:
    def __init__(self, rid, max_new):
        self.req = types.SimpleNamespace(req_id=rid)
        self.tokens, self.max_new = [], max_new
        self.steps = 0


class ScriptedSession:
    """Admits whatever is queued into free slots; each resident request
    gets two tokens at its first harvest (the admit token and the first
    decode token) and one at every later one; a request that reaches
    ``max_new`` is returned by that step and leaves its slot."""

    def __init__(self, slots=2):
        self._active = [None] * slots
        self._queue = []
        self._cur_len = np.zeros(slots, np.int32)
        self._inflight = None
        self.stats = types.SimpleNamespace(admitted=0)

    def submit(self, prompt, max_new, req_id):
        self._queue.append((req_id, max_new))

    @property
    def drained(self):
        return not self._queue and not any(self._active)

    def step(self):
        done = []
        for i, st in enumerate(self._active):
            if st is None:
                continue
            st.steps += 1
            st.tokens += [7] * (2 if st.steps == 1 else 1)
            if len(st.tokens) >= st.max_new:
                del st.tokens[st.max_new:]
                done.append(types.SimpleNamespace(
                    req_id=st.req.req_id, tokens=np.array(st.tokens),
                    finish_reason="length"))
                self._active[i] = None
        for i, st in enumerate(self._active):
            if st is None and self._queue:
                rid, m = self._queue.pop(0)
                self._active[i] = Slot(rid, m)
                self.stats.admitted += 1
        return done


def stream():
    for due, n in [(0.0, 4), (0.0, 3), (0.05, 2)]:
        yield due, np.zeros(5, np.int32), n
    yield 1e9, np.zeros(5, np.int32), 1      # never due in the test


def test_delivery_times_follow_the_async_lag():
    sess = ScriptedSession()
    drv = run.Client(sess, stream(), {"kind": "open_loop"}, 2,
                     lambda name: run._NullSpan())
    import time
    drv.t0 = time.perf_counter()
    while len([r for r in drv.reqs.values() if r["tokens"] is not None]) < 3:
        drv.step()
    ends = [s["t_end"] for s in drv.steps]
    starts = [s["t_start"] for s in drv.steps]
    r0, r1, r2 = drv.reqs[0], drv.reqs[1], drv.reqs[2]
    # admitted in step 0, tokens 1-2 at step 1, then one per step
    assert r0["admit"] == starts[0]
    assert r0["times"] == [ends[1], ends[1], ends[2], ends[3]]
    assert r1["times"] == [ends[1], ends[1], ends[2]]
    assert list(r0["tokens"]) == [7] * 4
    # the third request was due later: its first token counts from its due
    assert r2["due"] == pytest.approx(drv.t0 + 0.05)
    k = starts.index(r2["admit"])
    assert r2["times"] == [ends[k + 1], ends[k + 1]]
    assert r2["times"][0] - r2["due"] > 0


def test_end_to_end_from_the_records():
    sess = ScriptedSession()
    drv = run.Client(sess, stream(), {"kind": "open_loop"}, 2,
                     lambda name: run._NullSpan())
    import time
    drv.t0 = time.perf_counter()
    drv.run_until(drv.t0 + 0.2)
    out, attempted, failed = run.end_to_end(drv, drv.t0 - 1, drv.t0 + 0.2, 1.5)
    assert attempted == 3 and failed == 0
    ttft = [r["times"][0] - r["due"] for r in drv.reqs.values()]
    assert out["ttft_p90_s"][0] == pytest.approx(np.percentile(ttft, 90))
    gaps = [b - a for r in drv.reqs.values()
            for a, b in zip(r["times"], r["times"][1:])]
    assert out["itl_p99_s"][0] == pytest.approx(np.percentile(gaps, 99))
    assert out["setup_s"] == (1.5, "s")


def test_a_rehearsal_clock_counts_steps_not_wall_time():
    """On ``StepClock`` a step lasts 1/steps_per_s and a wait for an arrival
    jumps to it, so a slower host serves the same steps at the same times."""
    records = []
    for pause in (0.0, 0.002):
        sess = ScriptedSession()
        step = sess.step

        def slow_step(step=step, pause=pause):
            import time
            time.sleep(pause)
            return step()
        sess.step = slow_step
        drv = run.Client(sess, stream(), {"kind": "open_loop"}, 2,
                         lambda name: run._NullSpan(), run.StepClock(100))
        drv.t0 = drv.clock.now()
        drv.run_until(drv.t0 + 0.2)
        records.append((drv.steps, drv.reqs))
        assert all(s["t_end"] - s["t_start"] == pytest.approx(0.01)
                   for s in drv.steps)
        # the third request, due at 0.05, comes after the first two finished
        assert drv.reqs[2]["admit"] == pytest.approx(0.05)
        assert drv.clock.now() == pytest.approx(0.2)
    (steps_a, reqs_a), (steps_b, reqs_b) = records
    assert steps_a == steps_b
    assert [(r["admit"], r["times"]) for r in reqs_a.values()] == [
        (r["admit"], r["times"]) for r in reqs_b.values()]
