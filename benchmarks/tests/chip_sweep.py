#!/usr/bin/env python3
"""The rate sweep, for the chip: the arrivals cell at a list of rates, one
run after another in one process, to find the highest rate the tree sustains
without a growing queue. The configuration's `sustained_rate_per_s` is set
from what this prints; the benchmark's own runs never search.

    python3 benchmarks/tests/chip_sweep.py --workload flagship-5k.arrivals \
        --rates 150,200,250,300,350 --seconds 24 --seed 5
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.harness import cell

    real = cell.find_cell
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        def at_rate(bench, name, rate=rate):
            c, cfg, tr = real(bench, name)
            cfg = {**cfg, "sustained_rate_per_s": rate}
            if args.rehearse:
                cfg["rehearse"] = {**cfg["rehearse"],
                                   "sustained_rate_per_s": rate}
            return c, cfg, {**tr, "rate_share_of_sustained": 1.0}

        cell.find_cell = at_rate
        gc.unfreeze()
        _code, res = cell.run_cell(args.workload, args.seed + i,
                                   args.seconds, False,
                                   rehearse=args.rehearse)
        print(json.dumps({"offered_per_s": rate, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
