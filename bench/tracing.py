"""Reduce a JAX profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Its device
plane (``/device:TPU:0``) holds one line of XLA module executions (one event
per jitted program run) and one of XLA operations (fusions, custom calls such
as the Pallas kernels, copies); the host plane holds the benchmark's own
spans (``bench.step``, ``bench.submit``, ...) as ``TraceAnnotation`` events,
on the same clock.

* busy: the union of the operation intervals on the device; the window is
  from the trace's first to its last event of any kind;
* a module's device time: the sum of its module events whose name holds a
  given program name (``_decode_tick``, ``_admit_fused_paged``);
* a kernel's events: operation events that ``kernels/<kernel>.py`` matches.
  An operation event's name is its HLO instruction's text, shapes included:
  ``%approx_matmul_kernel_call.91 = s32[8,8192]{...} custom-call(u8[8,2048]
  ..., u8[2048,8192] ...)``; Pallas calls carry the name of the jitted
  wrapper that launches them.

Operations nest (a ``while`` holds the layer scan's body), so the breakdown
ranks operations by self time, the part of an event no event inside it
covers.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import List, Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start_ns: int
    end_ns: int

    @property
    def dur_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def _events(line) -> List[Event]:
    return [Event(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]


def op_kind(name: str) -> str:
    """``%copy.61 = bf16[1,1280,16,8,64]{...} copy(...)`` -> ``copy
    bf16[1,1280,16,8,64]``: the instruction without its number, and its
    result's shape."""
    head, _, rest = name.partition(" = ")
    base = head.lstrip("%").rsplit(".", 1)[0] if "." in head else head.lstrip("%")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{base} {shape}".strip()


def self_times(events):
    """(event, seconds no nested event covers), for events that nest."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    own = {id(e): e.end_ns - e.start_ns for e in evs}
    stack = []
    for e in evs:
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= stack[-1].end_ns:
            own[id(stack[-1])] -= e.end_ns - e.start_ns
        stack.append(e)
    return [(e, max(own[id(e)], 0) * 1e-9) for e in evs]


def union_seconds(intervals) -> float:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total * 1e-9


def gaps(intervals, lo: int, hi: int):
    """Idle intervals between merged busy intervals within [lo, hi]."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


class TraceData:
    def __init__(self, ops: List[Event], modules: List[Event],
                 spans: List[Event]):
        self.ops, self.modules, self.spans = ops, modules, spans
        times = [e.start_ns for e in ops + modules + spans] or [0]
        ends = [e.end_ns for e in ops + modules + spans] or [0]
        self.lo, self.hi = min(times), max(ends)
        # the window is what the host spans cover: the traced part of the run
        if spans:
            self.lo = min(e.start_ns for e in spans)
            self.hi = max(e.end_ns for e in spans)
        self.window_s = (self.hi - self.lo) * 1e-9
        self.busy_s = union_seconds(
            [(max(e.start_ns, self.lo), min(e.end_ns, self.hi)) for e in ops
             if e.end_ns > self.lo and e.start_ns < self.hi])

    def module_seconds(self, program: str) -> float:
        return sum(e.dur_s for e in self.modules if program in e.name)

    def kernel_events(self, kernel_mod) -> List[Event]:
        return [e for e in self.ops if kernel_mod.matches(e)]

    def breakdown(self, top: int = 10) -> dict:
        by_op = {}
        for e, own in self_times(self.ops):
            k = op_kind(e.name)
            by_op[k] = by_op.get(k, 0.0) + own
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        idle = {}
        busy = [(e.start_ns, e.end_ns) for e in self.ops]
        spans = sorted((e.start_ns, e.end_ns, e.name) for e in self.spans)
        starts = [s for s, _, _ in spans]
        for a, b in gaps(busy, self.lo, self.hi):
            # the host span (spans do not overlap) open at the gap's middle
            mid = (a + b) // 2
            i = bisect.bisect_right(starts, mid) - 1
            name = spans[i][2] if i >= 0 and mid < spans[i][1] else "no bench span"
            idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9
        gap_list = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gap_list]}


def from_profile(pd) -> TraceData:
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            if not plane.name.endswith(":0"):
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += _events(line)
                elif line.name == MODULES_LINE:
                    modules += _events(line)
        else:
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e.name.startswith(HOST_SPAN_PREFIX)]
    return TraceData(ops, modules, spans)


def find_xplane(directory) -> Optional[str]:
    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(directory) -> TraceData:
    import jax
    path = find_xplane(directory)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return from_profile(jax.profiler.ProfileData.from_file(path))
