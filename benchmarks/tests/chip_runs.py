#!/usr/bin/env python3
"""A set of runs of one cell, for the chip: the benchmark's own command once
per seed, each a new process as the driver starts them (this parent never
touches JAX), then each metric's median and spread (the contract's: distance
between the quartiles over the median).

    python3 benchmarks/tests/chip_runs.py --workload flagship-5k.backlog \
        --seeds 1,2,3,4,5,6 --seconds 40 --trace 0 --tag set1
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness.stats import spread   # noqa: E402 - no JAX in there


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--tag", default="set")
    args = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for seed in args.seeds.split(","):
        p = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload", args.workload,
             "--seed", seed, "--seconds", args.seconds, "--trace",
             args.trace], cwd=ROOT, capture_output=True, text=True)
        stem = os.path.join(out_dir, f"{args.tag}_{args.workload}_{seed}")
        with open(stem + ".out", "w") as f:
            f.write(p.stdout)
        with open(stem + ".err", "w") as f:
            f.write("\n".join(ln for ln in p.stderr.splitlines()
                              if ln.startswith("#")) + "\n")
        lines = p.stdout.strip().splitlines()
        last = json.loads(lines[-1]) if p.returncode == 0 and lines else {}
        failed_checks = [ln for ln in lines if ln.startswith("check")
                         and ln.endswith("FAILED")]
        rows.append(last)
        print(json.dumps({
            "seed": int(seed), "rc": p.returncode,
            "correct": last.get("correct"), "failed": last.get("failed"),
            "attempted": last.get("attempted"),
            **{k: round(v["value"], 4) for k, v in
               (last.get("metrics") or {}).items()},
            "idle": (round(1 - last["device"]["busy_s"]
                           / last["device"]["window_s"], 4)
                     if "busy_s" in last.get("device", {}) else None),
            "failed_checks": failed_checks}), flush=True)
        if p.returncode:
            print(p.stderr[-1500:], flush=True)
    names = sorted({k for r in rows for k in (r.get("metrics") or {})})
    for k in names:
        vals = [r["metrics"][k]["value"] for r in rows
                if k in (r.get("metrics") or {})]
        if len(vals) >= 2:
            print(json.dumps({
                "metric": k, "n": len(vals),
                "median": round(statistics.median(vals), 4),
                "spread": round(spread(vals), 4),
                "min": round(min(vals), 4), "max": round(max(vals), 4)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
