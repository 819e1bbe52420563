"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Device check: a TPU backend with as many chips as the cell asks for, and
   no ``REPRO_FORCE_INTERPRET``; otherwise exit 1 with no result.
2. Set-up (``setup_s``, from process start): weights made on the device from
   the seed in one jitted call (frozen to uint8 codes for ``approx``), the
   ``ServeSession`` built and warmed up, all from the compile cache kept in
   ``.jax_cache/`` of this checkout (or ``$JAX_COMPILATION_CACHE_DIR``).
3. A ramp that is discarded, then the window of ``--seconds``: the cell's
   traffic drives ``ServeSession.submit`` and ``ServeSession.step``; every
   token's delivery time is the wall time at the ``step()`` return that
   handed it to the host. Chat cells then drain the requests sent in the
   window, with arrivals still coming, before the session is closed.
4. ``correct``: a sample of the finished requests, drawn from the seed and
   holding the longest, goes through the configuration's plain reference
   (``reference.py`` unless it names another) once the session is freed.
   Each served token's gap is how far its reference logit lies below the
   reference's best; the widest gap
   (``served_logit_gap``) and the mean (``mean_served_logit_gap``) are
   compared with the limits in the cell's file, where it sets one. Every
   finished request must hold its ``max_new`` tokens, all in the
   vocabulary, every chat request sent in the window must have finished in
   the drain, and nothing may compile inside the window.
5. The last line of standard output is one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
   also ``breakdown``), and last ``checks``: each number compared, beside
   its limit. The same numbers are the last lines of standard error.

With ``--trace 1`` the profiler records the last ``TRACE_S`` seconds of the
window and the metrics are the cell's per-layer ones (``metrics/<name>.py``).
``--rehearse`` runs a tiny model on any backend for the self-tests and
reports no metrics; its ramp and window are counted in session steps
(``StepClock``), so a self-test reads the same on a fast host and a slow one.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402

TRACE_S = 8.0        # the profiled part of a --trace 1 window
REHEARSAL_STEPS_PER_S = 250   # a rehearsal's clock (StepClock)
REF_T, REF_S = 4096, 1024   # reference: packed positions, served tokens
GAPS = ("served_logit_gap", "mean_served_logit_gap")


class Refused(Exception):
    """The run cannot report: no chip, or the program could not be run."""


def device_check(jax, chips: int, rehearse: bool) -> None:
    if rehearse:
        return
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        raise Refused("REPRO_FORCE_INTERPRET is set: the kernels would run in "
                      "the interpreter, not on the chip")
    backend = jax.default_backend()
    if backend != "tpu":
        raise Refused(f"JAX backend is {backend!r}: this benchmark measures a TPU")
    n = len(jax.devices())
    if n < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds {n}")


def device_info(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# -- the load client ----------------------------------------------------------


class WallClock:
    """The host's clock: a run's window is ``--seconds`` of wall time."""

    now = staticmethod(time.perf_counter)

    def tick(self):
        pass

    def sleep_until(self, t: float):
        time.sleep(max(0.0, t - time.perf_counter()))


class StepClock:
    """A rehearsal's clock: it advances ``1 / steps_per_s`` with each
    ``step()`` of the session and jumps over a wait for an arrival, so what a
    rehearsal serves and checks is the same on a fast host and a slow one."""

    def __init__(self, steps_per_s: float):
        self.t, self.dt = 0.0, 1.0 / steps_per_s

    def now(self) -> float:
        return self.t

    def tick(self):
        self.t += self.dt

    def sleep_until(self, t: float):
        self.t = max(self.t, t)


class Client:
    """Feeds the cell's traffic to the session and records, for every
    request, when it was due, when it was admitted, and when each of its
    tokens reached the host, on ``clock``."""

    def __init__(self, sess, stream, mix: dict, num_slots: int, span,
                 clock=None):
        self.sess, self.stream = sess, stream
        self.clock = clock or WallClock()
        self.open_loop = mix["kind"] == "open_loop"
        self.backlog = mix.get("backlog_per_slot", 0) * num_slots
        self.span = span
        self.reqs = {}          # req_id -> record
        self.live = {}          # req_id -> the session's slot state
        self.steps = []         # one dict per step() call
        self.next_req = None
        self.t0 = None
        self.n_sub = 0

    def _record(self, due, prompt, max_new):
        rid = self.n_sub
        self.n_sub += 1
        self.reqs[rid] = {"due": due, "plen": len(prompt), "max_new": max_new,
                          "prompt": prompt, "admit": None, "times": [],
                          "tokens": None, "reason": None}
        self.sess.submit(prompt, max_new=max_new, req_id=rid)

    def _feed(self, now, arrivals: bool):
        with self.span("bench.submit"):
            if not self.open_loop:
                queued = self.n_sub - self.sess.stats.admitted
                while queued < self.backlog:
                    _, p, m = next(self.stream)
                    self._record(now, p, m)
                    queued += 1
                return
            while arrivals:
                if self.next_req is None:
                    self.next_req = next(self.stream)
                due = self.t0 + self.next_req[0]
                if due > now:
                    return
                _, p, m = self.next_req
                self.next_req = None
                self._record(due, p, m)

    def _harvest(self, t_start, t_end, finished):
        sess = self.sess
        for st in sess._active:
            if st is not None and st.req.req_id not in self.live:
                self.live[st.req.req_id] = st
                self.reqs[st.req.req_id]["admit"] = t_start
        for rid, st in list(self.live.items()):
            rec = self.reqs[rid]
            new = len(st.tokens) - len(rec["times"])
            rec["times"] += [t_end] * new
        for c in finished:
            rec = self.reqs[c.req_id]
            if rec["admit"] is None:
                rec["admit"] = t_start
            rec["times"] += [t_end] * (len(c.tokens) - len(rec["times"]))
            rec["tokens"], rec["reason"] = c.tokens, c.finish_reason
            self.live.pop(c.req_id, None)

    def step(self, arrivals: bool = True, t_stop: float = float("inf")):
        now = self.clock.now()
        self._feed(now, arrivals)
        if self.sess.drained:
            if not (self.open_loop and arrivals):
                return False
            with self.span("bench.wait_arrival"):
                if self.next_req is None:
                    self.next_req = next(self.stream)
                self.clock.sleep_until(min(self.t0 + self.next_req[0], t_stop))
            return True
        t_start = self.clock.now()
        with self.span("bench.step"):
            finished = self.sess.step()
        self.clock.tick()
        t_end = self.clock.now()
        self._harvest(t_start, t_end, finished)
        self.steps.append({"t_start": t_start, "t_end": t_end,
                           "queued": self.n_sub - self.sess.stats.admitted,
                           **self._decode_rows()})
        return True

    def _decode_rows(self) -> dict:
        """Rows of the decode chunk this step dispatched, and the positions
        their attention reads (the new token's included)."""
        fl = self.sess._inflight
        if fl is None:
            return {"rows": 0, "ctx": 0}
        live = [i for i, st in enumerate(fl.states) if st is not None]
        return {"rows": len(live),
                "ctx": int(sum(int(self.sess._cur_len[i]) for i in live))}

    def run_until(self, t_stop, arrivals: bool = True):
        while self.clock.now() < t_stop:
            self.step(arrivals, t_stop)


# -- window statistics ----------------------------------------------------------


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q))


def end_to_end(drv: Client, t_open: float, t_close: float,
               setup_s: float) -> tuple:
    """The end-to-end metrics, ``attempted`` and ``failed``."""
    window = t_close - t_open
    out = {"setup_s": (setup_s, "s")}
    if drv.open_loop:
        sent = [r for r in drv.reqs.values() if t_open <= r["due"] < t_close]
        done = [r for r in sent if r["tokens"] is not None]
        ttft = [r["times"][0] - r["due"] for r in sent if r["times"]]
        gaps = [b - a for r in sent for a, b in zip(r["times"], r["times"][1:])]
        if ttft:
            out["ttft_p90_s"] = (percentile(ttft, 90), "s")
        if gaps:
            out["itl_p99_s"] = (percentile(gaps, 99), "s")
        return out, len(sent), len(sent) - len(done)
    toks = sum(1 for r in drv.reqs.values() for t in r["times"]
               if t_open <= t < t_close)
    out["output_tokens_per_s"] = (toks / window, "tokens/s")
    done = [r for r in drv.reqs.values() if r["tokens"] is not None
            and t_open <= r["times"][-1] < t_close]
    return out, len(done), 0


# -- correctness ---------------------------------------------------------------


def malformed(reqs, vocab: int) -> int:
    """Finished requests that do not hold ``max_new`` in-vocabulary tokens."""
    bad = 0
    for r in reqs:
        t = r["tokens"]
        if (len(t) != r["max_new"] or r["reason"] != "length"
                or len(r["times"]) != len(t)
                or not ((t >= 0) & (t < vocab)).all()):
            bad += 1
    return bad


def sample(reqs, seed: int, T: int = REF_T, S: int = REF_S) -> list:
    """The longest finished request, then others in an order drawn from the
    seed, while they pack into ``T`` positions and ``S`` served tokens."""
    import numpy as np
    if not reqs:
        return []
    longest = max(range(len(reqs)),
                  key=lambda i: (len(reqs[i]["tokens"]), reqs[i]["plen"]))
    rng = np.random.default_rng([*harness.seed_key_parts(seed), 7])
    order = [longest] + [int(i) for i in rng.permutation(len(reqs))
                         if i != longest]
    out, pos, srv = [], 0, 0
    for i in order:
        r = reqs[i]
        n_pos = r["plen"] + len(r["tokens"]) - 1
        if pos + n_pos <= T and srv + len(r["tokens"]) <= S:
            out.append(r)
            pos += n_pos
            srv += len(r["tokens"])
    return out


def gaps(lg, tokens):
    """How far each chosen token's reference logit lies below the best."""
    import numpy as np
    lg = np.asarray(lg, np.float64)
    return lg.max(axis=1) - lg[np.arange(len(tokens)), tokens]


def reference_gap(ref, conf: dict, seed: int, picked: list,
                  precision: str = "reference") -> dict:
    """How far each served token's reference logit lies below the
    reference's best, over the picked requests: the widest gap and the mean;
    with ``precision="control"`` the same of the tokens the control ranks
    first, under ``control_``. Beside them, how many distinct tokens were
    served and the reference's median margin between its two best logits,
    which say how far a wrong token would read. ``ref`` is the
    configuration's reference module (``harness.reference_module``)."""
    import numpy as np
    key = harness.prng_key(seed)
    requests = [(r["prompt"], r["tokens"]) for r in picked]
    lg, tokens = ref.served_logits(conf, key, requests, REF_T, REF_S,
                                   "reference")
    lg = np.asarray(lg)
    g = gaps(lg, tokens)
    top2 = np.sort(np.asarray(lg, np.float64), axis=1)[:, -2:]
    out = {"tokens": len(tokens), "served_logit_gap": float(g.max()),
           "mean_served_logit_gap": float(g.mean()),
           "argmax_agree": float(np.mean(lg.argmax(1) == tokens)),
           "distinct_tokens": int(np.unique(tokens).size),
           "median_top2_margin": float(np.median(top2[:, 1] - top2[:, 0]))}
    if precision == "control":
        ctrl, _ = ref.served_logits(conf, key, requests, REF_T, REF_S, "control")
        gc_ = gaps(lg, np.asarray(ctrl).argmax(1))
        out["control_served_logit_gap"] = float(gc_.max())
        out["control_mean_served_logit_gap"] = float(gc_.mean())
    return out


def within(read: dict, limits: dict) -> bool:
    """The compared gaps are all within the cell's limits, and it sets one."""
    compared = [n for n in GAPS if limits.get(n) is not None]
    return bool(compared) and all(read[n] <= limits[n] for n in compared)


# -- the run -------------------------------------------------------------------


def rehearsal(conf: dict, mix: dict, root=harness.BENCH) -> tuple:
    """A tiny model and session for the self-tests, with the traffic clipped
    to fit it; every other part of the run is the cell's own. The sizes are
    the reference module's ``REHEARSAL``: each key is an architecture key
    (``harness.arch_keys``), and goes to the program too, or ``as_run``."""
    tiny = dict(harness.reference_module(conf, root).REHEARSAL)
    as_run = tiny.pop("as_run", {})
    keys = harness.arch_keys(conf, root)
    unmapped = sorted(set(tiny) - set(keys))
    if unmapped:
        raise ValueError(f"{conf['name']}: the rehearsal keys {unmapped} map "
                         "to no program attribute (harness.arch_keys)")
    conf = {**conf, **tiny, "as_run": {**conf["as_run"], **as_run}}
    conf["program_overrides"] = {**conf.get("program_overrides", {}),
                                 **{keys[k]: v for k, v in tiny.items()}}
    conf["session"] = dict(conf["session"], num_slots=4, max_len=96,
                           prompt_buckets=[32, 64])
    mix = dict(mix, ramp_s=0.5, drain_cap_s=60.0,
               prompt_tokens=dict(mix["prompt_tokens"], min=4, max=64, median=24),
               output_tokens=dict(mix["output_tokens"], min=2, max=32, median=8))
    return conf, mix


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, root=harness.BENCH, patch=None,
        control: bool = False, rate: float = None, check: bool = True,
        program_mode: str = None) -> dict:
    """One run of a cell; returns the result object. ``patch(prog)`` may
    alter the program under test (the self-tests break it with it);
    ``control`` judges, in the program's place, the gaps of the control's
    first-ranked tokens at the program's served positions (``control.py``;
    the program's own readings and verdict go under ``program``);
    ``program_mode`` runs the program in another execution mode against the same reference (the program's own
    lower-precision path as the control); ``rate`` overrides the cell's and
    ``check=False`` skips the reference (``sweep.py``)."""
    import jax
    import numpy as np

    cell = harness.load_cell(cell_name, root)
    conf = harness.load_config(cell["config"], root)
    mix = harness.load_traffic(cell["traffic"], root)
    limits = cell["limits"]
    bench = harness.benchmark(root)
    rate = rate or cell.get("rate_rps")
    if rehearse:
        conf, mix = rehearsal(conf, mix, root)
        rate = rate and 20.0
    device_check(jax, cell["chips"], rehearse)
    dev = device_info(jax)
    peaks = None
    if not rehearse:
        peaks = harness.load_json(root / "peaks.json").get(dev["kind"])
        if peaks is None:
            raise Refused(f"device kind {dev['kind']!r} is not in peaks.json")

    prog = harness.import_program()
    if patch is not None:
        patch(prog)
    cache_dir = None
    if not rehearse:
        cache_dir = prog.enable_compile_cache()
        # serve small programs from the cache too
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"bench: {cell_name} seed {seed} on {dev}; compile cache {cache_dir}",
          file=sys.stderr, flush=True)

    cfg = harness.model_config(prog, dict(conf, mode=program_mode or conf["mode"]),
                              root)
    key = harness.prng_key(seed)
    params = jax.jit(lambda k: harness.make_weights(prog, cfg, k))(key)
    jax.block_until_ready(params)
    skw = dict(conf["session"])
    num_slots = skw["num_slots"]
    skw["cache_dtype"] = jax.numpy.dtype(skw["cache_dtype"])
    skw["prompt_buckets"] = tuple(skw["prompt_buckets"])
    sess = prog.ServeSession(cfg, params, seed=0,
                             sampling=prog.SamplingConfig(eos_id=-1), **skw)
    sess.warmup()
    compiled_at_setup = prog.compile_stats()

    span = (jax.profiler.TraceAnnotation if trace
            else (lambda name: _NullSpan()))
    gen = harness.generator(mix, root)
    rng = np.random.default_rng([*harness.seed_key_parts(seed), 1])
    stream = gen.stream(mix, rng, cfg.vocab_size, rate)
    clock = StepClock(REHEARSAL_STEPS_PER_S) if rehearse else WallClock()
    drv = Client(sess, stream, mix, num_slots, span, clock)
    setup_s = time.perf_counter() - T_START

    # ramp (discarded), then the window
    drv.t0 = clock.now()
    drv.run_until(drv.t0 + mix["ramp_s"])
    stats0 = _counters(sess, clock)
    t_open = clock.now()
    t_close = t_open + seconds
    tr = None
    if trace:
        # the profiler covers the window's last TRACE_S seconds, so that
        # writing the trace out falls after the window
        drv.run_until(max(t_open, t_close - TRACE_S))
        tr = _Trace(jax, cell_name, clock)
        tr.start(sess)
    drv.run_until(t_close)
    if trace:
        tr.stop(sess)
    stats1 = _counters(sess, clock)
    if drv.open_loop and check:
        # drain what the window sent, with arrivals still coming
        cap = t_close + mix["drain_cap_s"]
        with span("bench.drain"):
            while clock.now() < cap and any(
                    r["tokens"] is None for r in drv.reqs.values()
                    if t_open <= r["due"] < t_close):
                drv.step(t_stop=cap)
    recompiles = {k: v - compiled_at_setup.get(k, 0)
                  for k, v in prog.compile_stats().items()
                  if v != compiled_at_setup.get(k, 0)}
    memory_peak = _peak_bytes(jax)
    t = clock.now()
    drv._harvest(t, t, [c for rid, c in sess.close().items()
                        if drv.reqs[rid]["tokens"] is None])

    metrics, attempted, failed = end_to_end(drv, t_open, t_close, setup_s)
    ref = harness.reference_module(conf, root)
    layer = {}
    if trace:
        tr.load()
        rec = RunRecord(conf, cfg, mix, drv, t_open, t_close, stats0, stats1,
                        tr, peaks, harness.kernel_costs(root), ref)
        names = [m["name"] for m in harness.cell_metrics(bench, cell_name, True)]
        for name, mod in harness.metric_readers(names, root).items():
            v = mod.read(rec)
            if v is not None:
                layer[name] = (v, next(m["unit"] for m in bench["per_layer"]
                                       if m["name"] == name))
    # free the program's state before the reference runs
    finished = [r for r in drv.reqs.values() if r["tokens"] is not None]
    del sess, params, drv.sess
    gc.collect()
    if not finished:
        raise Refused("no request finished: the window is too short to check")

    if not check:
        return {"metrics": metrics, "attempted": attempted, "failed": failed,
                "queue": [(s["t_start"] - t_open, s["queued"]) for s in drv.steps],
                "device": dict(dev, memory_peak_bytes=memory_peak)}
    read = reference_gap(ref, conf, seed, sample(finished, seed),
                         "control" if control else "reference")
    # with ``control``, the control's readings stand in the program's place
    judged = {n: read["control_" + n] for n in GAPS} if control else read
    checks = {name: {"value": judged[name], "limit": limits.get(name)}
              for name in GAPS}
    checks.update({
        "malformed_requests": {"value": malformed(finished, cfg.vocab_size), "limit": 0},
        "recompiles_in_window": {"value": sum(recompiles.values()), "limit": 0},
        "finished_requests": {"value": len(finished), "limit": 1},
    })
    if drv.open_loop:
        # a request sent in the window that the drain never finished
        checks["unfinished_requests"] = {"value": failed, "limit": 0}
    sound = all(checks[n]["value"] == 0 for n in
                ("malformed_requests", "recompiles_in_window",
                 "unfinished_requests") if n in checks)
    correct = sound and within(judged, limits)
    result = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in (layer if trace else metrics).items()},
        "device": dict(dev, memory_peak_bytes=memory_peak),
    }
    if trace:
        result["device"].update(busy_s=tr.busy_s, window_s=tr.window_s)
        result["breakdown"] = tr.breakdown()
    if rehearse:
        # a rehearsal reports no metrics; what it read is kept for the tests
        result["rehearsal_metrics"] = result.pop("metrics")
        result["metrics"] = {}
    if control:
        result["program"] = dict({k: read[k] for k in GAPS},
                                 correct=bool(sound and within(read, limits)))
    result["checks"] = dict(
        checks, reference_tokens={"value": read["tokens"], "limit": 1},
        argmax_agreement={"value": read["argmax_agree"], "limit": None},
        distinct_served_tokens={"value": read["distinct_tokens"], "limit": None},
        median_top2_margin={"value": read["median_top2_margin"], "limit": None})
    return result


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _counters(sess, clock) -> dict:
    s = sess.stats
    return {"wall_s": s.wall_s, "host_block_s": s.host_block_s,
            "prefill_tokens": s.prefill_tokens, "ticks": s.ticks,
            "admitted": s.admitted, "generated_tokens": s.generated_tokens,
            "admit_calls": s.admit_calls, "t": clock.now()}


def _peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


class RunRecord:
    """What a per-layer metric reader may read: the configuration, the
    client's request and step records, the session's counters at the window's
    ends, the trace, the peaks, the kernels' cost functions and the
    configuration's reference module (for its FLOP count)."""

    def __init__(self, conf, cfg, mix, drv, t_open, t_close, stats0, stats1,
                 trace, peaks, kernels, reference):
        self.conf, self.cfg, self.mix = conf, cfg, mix
        self.reference = reference
        self.reqs, self.step_log = drv.reqs, drv.steps
        self.t_open, self.t_close = t_open, t_close
        self.stats0, self.stats1 = stats0, stats1
        self.trace, self.peaks, self.kernels = trace, peaks, kernels

    def window_steps(self):
        return [s for s in self.step_log
                if self.t_open <= s["t_start"] < self.t_close]


class _Trace:
    """The profiler over the last part of the window, reduced by
    ``tracing.py``."""

    def __init__(self, jax, cell, clock):
        self.jax, self.clock = jax, clock
        self.dir = harness.OUT / f"trace-{cell}"

    def start(self, sess):
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)
        self.counters0 = _counters(sess, self.clock)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only: bench.* and the runtime's
        self.jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def stop(self, sess):
        self.jax.block_until_ready(sess.cache)
        self.counters1 = _counters(sess, self.clock)
        self.jax.profiler.stop_trace()

    def load(self):
        import tracing
        self.data = tracing.load(self.dir)
        self.busy_s = self.data.busy_s
        self.window_s = self.data.window_s

    def breakdown(self):
        return self.data.breakdown()


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny model on any backend; reports no metrics")
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     args.rehearse)
    except (Refused, FileNotFoundError, ImportError) as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        return 1
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
