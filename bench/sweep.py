"""Find a chat cell's knee: serve its traffic at several fixed rates in one
process and report, for each, the tails and whether the queue grew.

    python bench/sweep.py --workload <cell> --rates 0.5,1,2 --seconds 30

The knee is the highest rate at which the queue of requests waiting for a
slot does not grow through the window; the cell's rate is set at about four
fifths of it. No reference check runs here.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for rate in (float(r) for r in args.rates.split(",")):
        r = run.run(args.workload, args.seed, args.seconds, False, rate=rate,
                    check=False)
        q = [(t, n) for t, n in r["queue"] if 0 <= t < args.seconds]
        third = max(1, len(q) // 3)
        early = float(np.mean([n for _, n in q[:third]])) if q else 0.0
        late = float(np.mean([n for _, n in q[-third:]])) if q else 0.0
        print(json.dumps({"rate_rps": rate, "attempted": r["attempted"],
                          "failed": r["failed"],
                          "metrics": {k: v[0] for k, v in r["metrics"].items()},
                          "queued_first_third": early,
                          "queued_last_third": late}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
