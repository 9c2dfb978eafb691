#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload W --seed N --seconds S --trace 0|1

runs one cell of BENCHMARK.json once, on the machine it is started on, and
prints as its last line one JSON object: correct, attempted, failed, metrics
(the cell's end-to-end metrics with --trace 0, its per-layer metrics with
--trace 1), device, and with --trace 1 the breakdown. Everything else (the
checks beside their limits, the wave log, relists, lateness) is on earlier
lines; progress is on stderr. It needs a TPU: --rehearse runs the same code
at the configuration's `rehearse` size on whatever JAX finds, prints that
device, and is never quoted.
"""

import time

T_PROCESS = time.perf_counter()   # set-up is counted from here

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run at the rehearsal size on any device (CPU)")
    args = ap.parse_args(argv)

    from benchmarks.harness import cell

    try:
        code, result = cell.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            rehearse=args.rehearse, t_process=T_PROCESS)
    except cell.Deadline:
        print("benchmark: stopped by its deadline or SIGTERM; no result",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
