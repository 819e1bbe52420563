"""Read a cell's check on many seeds in one process, for the limits and for
the faults it has to catch.

    python bench/control.py --workload <cell> --seconds 51 \\
        --arm control@1,2,3 [--arm program@4,5 --arm fault=<name>@6 ...]

An arm is ``control``, ``program``, ``fault=<name>`` or ``mode=<mode>``, with
the seeds it runs after ``@``. Each seed is a whole run of the cell (its traffic, window and drain), judged
against the cell's limits by ``run.run``; one JSON line per seed, then a
summary line.

- ``control`` (the default): the program's own lower-precision path where
  the configuration names one (``control_mode``: ``exact_quant``, uint8 codes
  with exact integer products, for bf16 ``exact``), the whole cell run in
  that mode; otherwise the reference in the next lower precision (uint4
  codes for the uint8 approximate multiplier) put in the program's place,
  its first-ranked token's gap read at the program's served positions. The
  program's own readings of such a run are reported beside it.
- ``program``: the program as the configuration states it.
- ``fault=<name>``: the program with a fault of ``faults.py`` planted.
- ``mode=<mode>``: the program in another execution mode, against the
  cell's own reference (``exact_quant`` in an approx cell: exact products
  in place of the approximate multiplier).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import faults
import harness
import run


def arm_kwargs(arm: str, control_mode) -> dict:
    if arm == "control":
        return ({"program_mode": control_mode} if control_mode
                else {"control": True})
    if arm == "program":
        return {}
    kind, _, name = arm.partition("=")
    if kind == "fault":
        return {"patch": faults.FAULTS[name]}
    if kind == "mode":
        return {"program_mode": name}
    raise ValueError(f"unknown arm {arm!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--arm", action="append", required=True,
                    help="ARM@SEED,SEED,...; may be given more than once")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    control_mode = harness.load_config(cell["config"]).get("control_mode")
    rows = []
    for spec in args.arm:
        arm, _, seeds = spec.partition("@")
        for seed in (int(s) for s in seeds.split(",")):
            t = time.perf_counter()
            r = run.run(args.workload, seed, args.seconds, False,
                        **arm_kwargs(arm, control_mode))
            row = {"seed": seed, "arm": arm, "correct": r["correct"],
                   **{k: c["value"] for k, c in r["checks"].items()},
                   "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                   "run_s": time.perf_counter() - t}
            if "program" in r:
                row["program"] = r["program"]
            rows.append(row)
            print(json.dumps(row), flush=True)
    program = [r.get("program", r) for r in rows
               if r["arm"] == "program" or "program" in r]
    summary = {"workload": args.workload, "seeds": len(rows),
               "program_largest": {n: max((p[n] for p in program), default=None)
                                   for n in run.GAPS}}
    for arm in dict.fromkeys(r["arm"] for r in rows):
        mine = [r for r in rows if r["arm"] == arm]
        summary[arm] = {"correct": [r["correct"] for r in mine],
                        **{n + "_smallest": min(r[n] for r in mine)
                           for n in run.GAPS}}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
