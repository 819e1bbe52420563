"""Cut a recorded profiler trace down to a small XSpace text proto that keeps
what ``program_trace.py`` reads: a run of whole ``_decode_tick`` module events
with their operations (the device plane's module and op lines, without
stats), the ``serve.*`` host spans among them (no ``bench.*`` span: part of
one would set the window), and a ``/host:metadata`` plane whose ``Hlo Proto``
stats keep, for each module cut, only its instructions' names and
``op_name``s.

    python bench/tests/make_scope_fixture.py <trace.xplane.pb> <out.pbtxt> \
        --program _decode_tick --skip 20 --ticks 1

``--skip`` module events of ``--program`` are passed over, then ``--ticks``
of them are kept whole, from the first one's start to the last one's end.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

import jax

from make_trace_fixture import _q

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import program_trace  # noqa: E402
import tracing  # noqa: E402


# -- protobuf wire format, the writing side of program_trace.fields -------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def message(*fields) -> bytes:
    """Serialize (field number, value) pairs: ints as varints, strings and
    bytes as length-delimited fields."""
    out = bytearray()
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else bytes(v)
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return bytes(out)


def hlo_proto(module: str, ops: dict) -> bytes:
    """An ``HloProto`` holding one computation whose instructions carry only
    a name and an ``op_name``."""
    insts = [(2, message((1, name), (7, message((2, op)))))
             for name, op in ops.items()]
    return message((1, message((1, module), (3, message((1, "main"), *insts)))))


def _escape(b: bytes) -> str:
    return "".join(chr(c) if 32 <= c < 127 and c not in (34, 92) else f"\\{c:03o}"
                   for c in b)


def metadata_plane(protos: dict) -> str:
    """The ``/host:metadata`` plane: one event metadata per module event
    name, its ``Hlo Proto`` stat the serialized bytes."""
    metas = [f'event_metadata {{ key: {i} value {{ id: {i} name: '
             f'{_q(name)} stats {{ metadata_id: 1 '
             f'bytes_value: "{_escape(blob)}" }} }} }}'
             for i, (name, blob) in enumerate(protos.items(), 1)]
    return (f'planes {{ name: "{program_trace.METADATA_PLANE}" ' + " ".join(metas)
            + f' stat_metadata {{ key: 1 value {{ id: 1 name: '
            f'"{program_trace.HLO_PROTO_STAT}" }} }} }}\n')


def plane(name: str, lines, t0_ns: int = 0) -> str:
    """A plane of named lines of events (name, start ns, end ns, {stat: int})."""
    names, stat_ids, body = {}, {}, []
    for lid, (lname, events) in enumerate(lines, 1):
        evs = []
        for ename, start, end, stats in events:
            mid = names.setdefault(ename, len(names) + 1)
            st = " ".join(f"stats {{ metadata_id: {stat_ids.setdefault(k, len(stat_ids) + 1)} "
                          f"int64_value: {int(v)} }}" for k, v in stats.items())
            evs.append(f"events {{ metadata_id: {mid} offset_ps: {(start - t0_ns) * 1000} "
                       f"duration_ps: {(end - start) * 1000} {st}}}")
        body.append(f"lines {{ id: {lid} name: {_q(lname)} timestamp_ns: {t0_ns} "
                    + " ".join(evs) + " }")
    meta = [f"event_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} }} }}"
            for n, i in names.items()]
    smeta = [f"stat_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} }} }}"
             for n, i in stat_ids.items()]
    return f"planes {{ name: {_q(name)} " + " ".join(body + meta + smeta) + " }\n"


def span_plane(name: str, spans, t0_ns: int = 0) -> str:
    """A host plane of one line of spans (name, start ns, end ns, {stat: int})."""
    return plane(name, [("python", spans)], t0_ns)


def cut(pd, xspace: bytes, t0_ns: int, t1_ns: int) -> str:
    """The range's device modules and operations of chip 0, its host spans,
    and the cut modules' reduced HLO."""
    lines = {tracing.MODULES_LINE: [], tracing.OPS_LINE: []}
    spans = []
    for p in pd.planes:
        for line in p.lines:
            for e in line.events:
                if not (e.start_ns >= t0_ns and e.end_ns <= t1_ns):
                    continue
                ev = (e.name, int(e.start_ns), int(e.end_ns))
                if p.name == tracing.DEVICE_PREFIX + "0":
                    if line.name in lines:
                        lines[line.name].append(ev + ({},))
                elif e.name.startswith(program_trace.PROGRAM_SPAN_PREFIX):
                    spans.append(ev + ({k: v for k, v in e.stats
                                        if isinstance(v, int)},))
    text = plane(tracing.DEVICE_PREFIX + "0", list(lines.items()), t0_ns)
    if spans:
        text += span_plane("/host:CPU", spans, t0_ns)
    modules = {e[0] for e in lines[tracing.MODULES_LINE]}
    op_events = {program_trace.instruction(e[0]) for e in lines[tracing.OPS_LINE]}
    protos = {}
    for name, blob in program_trace.hlo_protos(xspace).items():
        if name in modules:
            ops = program_trace.op_names(blob)
            protos[name] = hlo_proto(name, {k: v for k, v in ops.items()
                                            if k in op_events})
    return text + (metadata_plane(protos) if protos else "")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--program", default="_decode_tick")
    ap.add_argument("--skip", type=int, default=20)
    ap.add_argument("--ticks", type=int, default=1)
    args = ap.parse_args(argv)
    with open(args.trace, "rb") as f:
        xspace = f.read()
    pd = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    mods = sorted((int(e.start_ns), int(e.end_ns)) for p in pd.planes
                  if p.name.startswith("/device:TPU:0") for ln in p.lines
                  if ln.name == "XLA Modules" for e in ln.events
                  if args.program in e.name)
    run = mods[args.skip:args.skip + args.ticks]
    text = cut(pd, xspace, run[0][0], run[-1][1])
    with open(args.out, "w") as f:
        f.write(text)
    jax.profiler.ProfileData.from_text_proto(text)   # reads back
    return 0


if __name__ == "__main__":
    sys.exit(main())
