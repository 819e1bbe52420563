"""Cut a recorded profiler trace down to a small XSpace text proto that
``jax.profiler.ProfileData.from_text_proto`` reads back: the device plane's
module and op lines and the host's ``bench.*`` spans, within a time range.

    python bench/tests/make_trace_fixture.py <trace.xplane.pb> <out.pbtxt> \
        --start-ms 0 --ms 40
"""
from __future__ import annotations

import argparse
import json
import sys

import jax


def _q(s: str) -> str:
    return json.dumps(s)


def cut(pd, t0_ns: int, t1_ns: int) -> str:
    out = []
    pid = 0
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            keep = [e for e in line.events
                    if e.start_ns >= t0_ns and e.end_ns <= t1_ns
                    and (plane.name.startswith("/device:TPU:")
                         or e.name.startswith("bench."))]
            if keep:
                lines.append((line.name, keep))
        if not lines:
            continue
        pid += 1
        names, stat_names = {}, {}
        body = []
        for lid, (lname, events) in enumerate(lines, 1):
            ev_txt = []
            for e in events:
                mid = names.setdefault(e.name, len(names) + 1)
                st = []
                for k, v in e.stats:
                    sid = stat_names.setdefault(str(k), len(stat_names) + 1)
                    if isinstance(v, str):
                        val = f"str_value: {_q(v)}"
                    elif isinstance(v, float):
                        val = f"double_value: {v!r}"
                    elif isinstance(v, int):
                        val = f"int64_value: {v}"
                    else:
                        continue
                    st.append(f"stats {{ metadata_id: {sid} {val} }}")
                ev_txt.append(
                    f"events {{ metadata_id: {mid} "
                    f"offset_ps: {int(round((e.start_ns - t0_ns) * 1000))} "
                    f"duration_ps: {int(round(e.duration_ns * 1000))} "
                    + " ".join(st) + " }")
            body.append(f"lines {{ id: {lid} name: {_q(lname)} "
                        f"timestamp_ns: {t0_ns} " + " ".join(ev_txt) + " }")
        meta = [f"event_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} }} }}"
                for n, i in names.items()]
        smeta = [f"stat_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} }} }}"
                 for n, i in stat_names.items()]
        out.append(f"planes {{ id: {pid} name: {_q(plane.name)} "
                   + " ".join(body + meta + smeta) + " }")
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--start-ms", type=float, default=0.0)
    ap.add_argument("--ms", type=float, default=40.0)
    args = ap.parse_args(argv)
    pd = jax.profiler.ProfileData.from_file(args.trace)
    starts = [e.start_ns for p in pd.planes if p.name.startswith("/device:TPU:")
              for ln in p.lines for e in ln.events]
    t0 = int(min(starts)) + int(args.start_ms * 1e6)
    text = cut(pd, t0, t0 + int(args.ms * 1e6))
    with open(args.out, "w") as f:
        f.write(text)
    jax.profiler.ProfileData.from_text_proto(text)   # reads back
    return 0


if __name__ == "__main__":
    sys.exit(main())
