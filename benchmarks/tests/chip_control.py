#!/usr/bin/env python3
"""Control runs, for the chip: a cell at its own size with one guarantee
broken underneath (controls.py). Every run must print `correct: false`.

    python3 benchmarks/tests/chip_control.py --workload flagship-5k.backlog \
        --control ignore_required_affinity --seeds 11,12,13 --seconds 40

One process, one run after another (the chip belongs to one process). The
last line sums up: how many runs came out not correct, and by which check.
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
    ap.add_argument("--control", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.harness import cell
    from benchmarks.tests import controls

    real = cell.find_cell

    def quick(bench, name):   # a lost pod never lands: a short settle
        c, cfg, tr = real(bench, name)
        return c, cfg, {**tr, "settle_s": min(tr["settle_s"], 10)}

    cell.find_cell = quick
    control = controls.CONTROLS[args.control]
    if control is controls.lower_commit_precision:
        # changes what the engine compiles: in place before the warm-up
        # traces the cycle, not once the measured scheduler is built
        control(None, None)
        control = None
    runs = []
    for seed in (int(s) for s in args.seeds.split(",")):
        gc.unfreeze()
        _code, res = cell.run_cell(
            args.workload, seed, args.seconds, False, rehearse=args.rehearse,
            sabotage=control)
        print(json.dumps({"seed": seed, **res}), flush=True)
        runs.append(res["correct"])
    print(json.dumps({"workload": args.workload, "control": args.control,
                      "runs": len(runs),
                      "not_correct": sum(1 for c in runs if not c)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
