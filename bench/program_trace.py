"""What the program itself marks in a profiler trace: its name scopes on the
device and its ``serve.*`` spans on the host.

* Device: the model's layers run under ``jax.named_scope`` (``SCOPES``, the
  names every family marks: ``embed``, ``norm``, ``attention``, ``kv_write``,
  ``dense``, ``mlp``, ``sample``; a configuration's reference module adds the
  names its architecture's own layers run under as its ``SCOPES``), which
  sits in each HLO instruction's ``op_name`` metadata. The trace carries the
  optimized HLO of every module it ran (``/host:metadata`` plane, one
  ``Hlo Proto`` stat per module, keyed by the module event's name such as
  ``jit__decode_tick(12)``); an operation event's name starts with its
  instruction's name (``%fusion.3 = ...``), so each operation maps to the
  innermost program scope on its ``op_name`` path, or to none: what XLA adds
  itself, such as the layer scan's copies.
* Host: ``ServeSession.step`` opens ``serve.step`` and, inside it,
  ``serve.admit``, ``serve.dispatch``, ``serve.harvest_wait`` and
  ``serve.accept`` (``jax.profiler.TraceAnnotation``, on the device's clock).

``tracing.py`` is left to read what it read before: the ``bench.*`` spans
still define the window, and nothing here changes ``window_s``, ``busy_s``
or ``breakdown()``. A trace of a program without scopes or spans reads
``None`` from every reader here.
"""
from __future__ import annotations

import bisect
import sys
from typing import Dict, List, Optional

import tracing

SCOPES = ("embed", "norm", "attention", "kv_write", "dense", "mlp", "sample")
PROGRAM_SPAN_PREFIX = "serve."
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
CLIENT = "client"        # an idle gap while no serve.* span is open


# -- protobuf wire format: just enough of XSpace and HloProto ------------------


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, value) of one serialized message: an int for varint and
    fixed-width fields, a memoryview for length-delimited ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def hlo_protos(xspace: bytes) -> Dict[str, bytes]:
    """Module event name -> its serialized ``HloProto``, from the metadata
    plane (XSpace.planes = 1; XPlane name = 2, event_metadata = 4,
    stat_metadata = 5; XEventMetadata name = 2, stats = 5; XStat
    metadata_id = 1, bytes_value = 6)."""
    out = {}
    for f, plane in fields(xspace):
        if f != 1:
            continue
        name, metas, stat_names = None, [], {}
        for g, v in fields(plane):
            if g == 2:
                name = _str(v)
            elif g == 4:
                metas.append(v)
            elif g == 5:
                stat = dict(fields(v)).get(2)     # map entry value
                if stat is not None:
                    sm = dict(fields(stat))
                    stat_names[sm.get(1, 0)] = _str(sm.get(2, b""))
        if name != METADATA_PLANE:
            continue
        hlo_id = next((k for k, n in stat_names.items()
                       if n == HLO_PROTO_STAT), None)
        for entry in metas:
            em = dict(fields(entry)).get(2)
            if em is None:
                continue
            ev_name, blob = None, None
            for g, v in fields(em):
                if g == 2:
                    ev_name = _str(v)
                elif g == 5:
                    st = dict(fields(v))
                    if st.get(1) == hlo_id and 6 in st:
                        blob = bytes(st[6])
            if ev_name and blob is not None:
                out[ev_name] = blob
    return out


def op_names(hlo_proto: bytes) -> Dict[str, str]:
    """Instruction name -> ``op_name`` over every computation of a module
    (HloProto.hlo_module = 1; HloModuleProto computations = 3;
    HloComputationProto instructions = 2; HloInstructionProto name = 1,
    metadata = 7; OpMetadata op_name = 2)."""
    out = {}
    for f, module in fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in fields(module):
            if g != 3:
                continue
            for h, inst in fields(comp):
                if h != 2:
                    continue
                name, op = None, ""
                for k, v in fields(inst):
                    if k == 1:
                        name = _str(v)
                    elif k == 7:
                        op = _str(dict(fields(v)).get(2, b""))
                if name is not None:
                    out[name] = op
    return out


def innermost_scope(op_name: str, scopes=SCOPES) -> Optional[str]:
    """The last of ``scopes`` on an ``op_name`` path, or ``None``. A scope
    under a transformation reads ``vmap(sample)`` or ``transpose(jvp(dense))``
    there."""
    for part in reversed(op_name.split("/")):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part in scopes:
            return part
    return None


def program_of(module: str) -> str:
    """The program of a module event's name: ``jit__decode_tick(12)`` reads
    ``_decode_tick``."""
    if module.endswith(")") and "(" in module:
        module = module[:module.rindex("(")]
    return module[len("jit_"):] if module.startswith("jit_") else module


def instruction(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


# -- the trace -----------------------------------------------------------------


class ProgramTrace:
    """A ``tracing.TraceData`` with the program's own marks beside it:
    ``program_spans`` (host ``serve.*`` events) and, per module event name,
    each instruction's ``op_name``, read against the run's ``scopes``."""

    def __init__(self, data: tracing.TraceData, program_spans: List[tracing.Event],
                 module_ops: Dict[str, Dict[str, str]], scopes):
        self.data = data
        self.scopes = tuple(scopes)
        self.program_spans = sorted(
            (e for e in program_spans
             if e.start_ns >= data.lo and e.end_ns <= data.hi),
            key=lambda e: (e.start_ns, -e.end_ns))
        self._starts = [e.start_ns for e in self.program_spans]
        self.module_ops = module_ops
        self._own = None
        self._by_scope: Dict[str, Dict[Optional[str], float]] = {}

    # device
    def _op_seconds(self, program: str) -> Dict[Optional[str], float]:
        """Self seconds of the operations inside ``program``'s module events,
        by innermost program scope; empty when the trace holds no HLO for
        them."""
        if program in self._by_scope:
            return self._by_scope[program]
        if self._own is None:
            self._own = tracing.self_times(self.data.ops)
        self._by_scope[program] = {}
        mods = sorted((m for m in self.data.modules if program in m.name),
                      key=lambda m: m.start_ns)
        if not mods or any(m.name not in self.module_ops for m in mods):
            return {}
        out: Dict[Optional[str], float] = {}
        j = 0
        for e, own in self._own:          # sorted by start
            while j < len(mods) and mods[j].end_ns <= e.start_ns:
                j += 1
            if j == len(mods):
                break
            m = mods[j]
            if e.start_ns < m.start_ns or e.end_ns > m.end_ns:
                continue
            scope = innermost_scope(self.module_ops[m.name].get(
                instruction(e.name), ""), self.scopes)
            out[scope] = out.get(scope, 0.0) + own
        self._by_scope[program] = out
        return out

    def scope_seconds(self, program: str, scope: Optional[str]) -> Optional[float]:
        """Device self seconds of ``program``'s operations whose innermost
        program scope is ``scope`` (``None``: no program scope); ``None``
        when the program carries no scopes at all."""
        by = self._op_seconds(program)
        if not any(s is not None for s in by):
            return None
        return by.get(scope, 0.0)

    def calls(self, program: str) -> int:
        """``program``'s module events in the trace: one per call."""
        return sum(1 for m in self.data.modules if program in m.name)

    # host
    def steps(self) -> List[tracing.Event]:
        return [e for e in self.program_spans if e.name == "serve.step"]

    def span_self_seconds(self, name: str) -> float:
        """Seconds inside spans named ``name`` that no nested program span
        covers, over the window."""
        return sum(own for e, own in tracing.self_times(self.program_spans)
                   if e.name == name)

    def innermost_span(self, t_ns: int) -> str:
        """The innermost program span open at ``t_ns``, or ``client``.
        Spans nest, so walking back from the last one that starts by
        ``t_ns``, the first still open is the innermost; a ``serve.step``
        passed closed means none is open."""
        i = bisect.bisect_right(self._starts, t_ns) - 1
        while i >= 0:
            e = self.program_spans[i]
            if e.end_ns > t_ns:
                return e.name
            if e.name == "serve.step":
                break
            i -= 1
        return CLIENT

    def idle_gaps(self):
        """(start s from the window's open, seconds, innermost program span
        at the gap's middle) of every device-idle gap in the window."""
        d = self.data
        busy = [(e.start_ns, e.end_ns) for e in d.ops]
        return [((a - d.lo) * 1e-9, (b - a) * 1e-9,
                 self.innermost_span((a + b) // 2))
                for a, b in tracing.gaps(busy, d.lo, d.hi)]


def from_bytes(xspace: bytes, scopes) -> ProgramTrace:
    import jax
    pd = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith(tracing.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            spans += [e for e in tracing._events(line)
                      if e.name.startswith(PROGRAM_SPAN_PREFIX)]
    data = tracing.from_profile(pd)
    wanted = {m.name for m in data.modules}
    module_ops = {name: op_names(blob)
                  for name, blob in hlo_protos(xspace).items() if name in wanted}
    return ProgramTrace(data, spans, module_ops, scopes)


def load(directory, scopes) -> ProgramTrace:
    path = tracing.find_xplane(directory)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    with open(path, "rb") as f:
        return from_bytes(f.read(), scopes)


# -- what the readers share ------------------------------------------------------


def of(rec) -> Optional[ProgramTrace]:
    """The run's program trace, read once against ``SCOPES`` and the
    configuration's reference module's own ``SCOPES``; the first read prints
    the decode and admit programs' time by scope and the idle gaps by program
    span to stderr."""
    if rec.trace is None:
        return None
    pt = getattr(rec, "_program_trace", None)
    if pt is None:
        pt = load(rec.trace.dir, SCOPES + tuple(rec.reference.SCOPES))
        rec._program_trace = pt
        report(pt, rec, sys.stderr)
    return pt


def decode_scope_ms(rec, scope: Optional[str]) -> Optional[float]:
    """Device ms per decode tick of ``_decode_tick``'s operations in
    ``scope`` (``None``: in no program scope), over the traced window."""
    pt = of(rec)
    if pt is None:
        return None
    ticks = rec.trace.counters1["ticks"] - rec.trace.counters0["ticks"]
    sec = pt.scope_seconds("_decode_tick", scope)
    if ticks <= 0 or sec is None:
        return None
    return 1000.0 * sec / ticks


def program_scope_ms(rec, program: str, scope: Optional[str]) -> Optional[float]:
    """Device ms per call of ``program``'s operations in ``scope`` (``None``:
    in no program scope), over the traced window; a call is one of
    ``program``'s module events."""
    pt = of(rec)
    if pt is None:
        return None
    calls = pt.calls(program)
    sec = pt.scope_seconds(program, scope)
    if not calls or sec is None:
        return None
    return 1000.0 * sec / calls


def span_ms_per_step(rec, name: str) -> Optional[float]:
    """Self ms of the ``name`` spans per traced ``serve.step``."""
    pt = of(rec)
    if pt is None or not pt.steps():
        return None
    return 1000.0 * pt.span_self_seconds(name) / len(pt.steps())


def _scope_line(pt: ProgramTrace, program: str, n: int, per: str, out) -> None:
    """``program``'s device ms per ``per`` by scope, over ``n`` of them."""
    by = {s: pt.scope_seconds(program, s) for s in pt.scopes + (None,)}
    if by[None] is None or n <= 0:
        return
    module = pt.data.module_seconds(program)
    parts = ", ".join(f"{s or 'unscoped'} {1000.0 * v / n:.3f}"
                      for s, v in by.items())
    print(f"program scopes, {program} ms per {per} over {n} {per}s: "
          f"{parts}; sum {1000.0 * sum(by.values()) / n:.3f} of module "
          f"{1000.0 * module / n:.3f}", file=out, flush=True)


def report(pt: ProgramTrace, rec, out) -> None:
    ticks = rec.trace.counters1["ticks"] - rec.trace.counters0["ticks"]
    _scope_line(pt, "_decode_tick", ticks, "tick", out)
    for program in sorted({program_of(m) for m in pt.module_ops} - {"_decode_tick"}):
        _scope_line(pt, program, pt.calls(program), "call", out)
    steps = pt.steps()
    if steps:
        parts = ", ".join(
            f"{n} {1000.0 * pt.span_self_seconds(n) / len(steps):.4f}"
            for n in ("serve.step", "serve.admit", "serve.dispatch",
                      "serve.harvest_wait", "serve.accept"))
        outside = (sum(e.dur_s for e in steps)
                   - sum(e.dur_s for e in pt.program_spans
                         if e.name == "serve.harvest_wait"))
        c0, c1 = rec.trace.counters0, rec.trace.counters1
        timers = ""
        if "wall_s" in c0:
            # the session's own timers over the same traced steps
            host = ((c1["wall_s"] - c0["wall_s"])
                    - (c1["host_block_s"] - c0["host_block_s"]))
            timers = f" (session timers {1000.0 * host / len(steps):.4f})"
        print(f"program spans, self ms per step over {len(steps)} steps: {parts}; "
              f"serve.step outside serve.harvest_wait "
              f"{1000.0 * outside / len(steps):.4f}{timers}", file=out, flush=True)
    kernels = getattr(rec, "kernels", None) or {}
    if kernels:
        print("kernel events in the window: " + ", ".join(
            f"{k} {len(pt.data.kernel_events(m))}" for k, m in kernels.items()),
            file=out, flush=True)
    gaps = pt.idle_gaps()
    total: Dict[str, float] = {}
    for _, dur, name in gaps:
        total[name] = total.get(name, 0.0) + dur
    print("idle gaps by program span (s): " + (", ".join(
        f"{n} {s:.6f}" for n, s in sorted(total.items(), key=lambda kv: -kv[1]))
        or "none"), file=out, flush=True)
    for at, dur, name in gaps:
        if dur > 1e-3:
            print(f"idle gap over 1 ms: {dur * 1000:.3f} ms at {at:.4f} s, in {name}",
                  file=out, flush=True)
