"""Shared arithmetic of the per-layer metric readers (``metrics/*.py``).

A reader returns ``None`` when its run has nothing for it to read; the
harness then leaves the metric out of the result line."""
from __future__ import annotations


def host_ms_per_step(rec):
    """Host seconds inside ``ServeSession.step`` not spent blocked on the
    device, per step, over the window (the session's own timers)."""
    n = len(rec.window_steps())
    if not n:
        return None
    s0, s1 = rec.stats0, rec.stats1
    host = ((s1["wall_s"] - s0["wall_s"])
            - (s1["host_block_s"] - s0["host_block_s"]))
    return 1000.0 * host / n


def device_idle_pct(rec):
    t = rec.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def traced_delta(rec, counter):
    return rec.trace.counters1[counter] - rec.trace.counters0[counter]


def traced_steps(rec):
    t0, t1 = rec.trace.counters0["t"], rec.trace.counters1["t"]
    return [s for s in rec.step_log if t0 <= s["t_start"] < t1]


def kernel_roofline(rec, kernel: str):
    """Least time the chip could take for the kernel's traced calls, over
    their device time, in %; ``None`` when the trace holds none of them."""
    if rec.peaks is None or rec.trace is None:
        return None
    mod = rec.kernels[kernel]
    events = rec.trace.data.kernel_events(mod)
    dur = sum(e.dur_s for e in events)
    if not events or dur <= 0:
        return None
    least = mod.least_seconds(rec, events, rec.peaks)
    if least is None:
        return None
    return 100.0 * least / dur


def mfu(rec):
    """Model FLOPs of the prompt tokens admitted and the output tokens
    delivered in the window, over the window times the bf16 peak, in %; the
    FLOPs of a span of positions are the configuration's reference module's
    ``model_flops``."""
    if rec.peaks is None:
        return None
    model_flops, conf = rec.reference.model_flops, rec.conf
    flops = 0.0
    for r in rec.reqs.values():
        if r["admit"] is not None and rec.t_open <= r["admit"] < rec.t_close:
            flops += model_flops(conf, 0, r["plen"])
        # output token j > 0 comes from the decode position plen + j - 1
        # (token 0 is the prefill's, counted with the prompt)
        for j, t in enumerate(r["times"]):
            if j and rec.t_open <= t < rec.t_close:
                p = r["plen"] + j - 1
                flops += model_flops(conf, p, p + 1)
    window = rec.t_close - rec.t_open
    return 100.0 * flops / (window * rec.peaks["bf16_flops_per_s"])
