#!/usr/bin/env python
"""Bench trend check: diff the two newest BENCH_rNN.json artifacts.

The post-run check the bench docs prescribe (`python bench.py --trend`, or
this script directly): loads the newest two artifacts, prints per-stage
metric deltas (pods_per_sec, cycle_seconds, and every METRIC_BUDGETS metric
for the stage), and exits NONZERO when a budget metric regressed beyond the
tolerance — so a perf PR whose bench run quietly lost a budgeted property
fails loudly at the trend gate, not three PRs later in a verdict.

Regression direction follows the budget op: a "<=" metric (cycle seconds,
overhead pct, lost pods) regresses UP; a ">=" metric (speedups, collapse
ratios, proof counters) regresses DOWN. `pods_per_sec` is always checked
(">=" semantics). Tolerance default 25% (shared CI boxes are noisy; the
absolute budgets in bench.py remain the hard floor — this gate catches
drift BETWEEN runs that stays inside them).

The durability stage (ISSUE 19) rides the same machinery: its
`recovery_seconds` and `wal_write_overhead_pct` are time-like (gated
within a backend, informational across backends), while `rv_continuity`,
`torn_tail_ok`, and `recovered_objects` are invariants that gate on every
backend.

The fleet-flagship stage (ISSUE 20) splits the same way: `pods_per_sec`
and `cycle_seconds` are time-like (its CPU numbers come from the 8-way
VIRTUAL mesh — a real-accelerator run records against the artifact's
`real_accel_cycle_budget_s` instead, and cross-backend pairs are
annotated, not gated), while `fleet_dispatches_per_tick`, `bit_equal`,
`bit_equal_tenants_checked`, `node_shards`, `lost_pods`, and
`double_bound` are invariants of the 2-D mesh contract that gate on every
backend.

Usage:
    python scripts/bench_trend.py [--dir REPO] [--tolerance 0.25]
    python bench.py --trend [same flags]

Artifacts may be either the raw bench summary ({"metric", "value",
"detail": {"stages": [...]}}) or a driver capture wrapping one under
"parsed" (parsed: null — a crashed run — is skipped with a warning).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

_NUM = re.compile(r"BENCH_r(\d+)\.json$")


def find_artifacts(directory: str):
    """BENCH_rNN.json paths sorted by NN ascending."""
    out = []
    for name in os.listdir(directory):
        m = _NUM.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(directory, name)))
    return [p for _, p in sorted(out)]


def load_stages(path: str):
    """{(kind, nodes, pods): stage record} from one artifact, or None when
    the artifact holds no parsed summary (a crashed run's capture)."""
    with open(path) as f:
        doc = json.load(f)
    if "parsed" in doc and "detail" not in doc:
        doc = doc.get("parsed")
    if not isinstance(doc, dict):
        return None
    stages = (doc.get("detail") or {}).get("stages")
    if not isinstance(stages, list):
        return None
    out = {}
    for r in stages:
        if isinstance(r, dict) and r.get("ok"):
            out[(r.get("kind", "flagship"), r.get("nodes"),
                 r.get("pods"))] = r
    return out


def _budget_metrics(kind, nodes):
    """The budgeted metric → direction map for one stage shape, sourced
    from bench.METRIC_BUDGETS so the trend gate and the absolute budgets
    can never name different metrics."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from bench import METRIC_BUDGETS
    except Exception:  # noqa: BLE001 - standalone checkout without bench
        return {}
    return {m: op for m, (op, _bound)
            in (METRIC_BUDGETS.get((kind, nodes)) or {}).items()}


def _regressed(op: str, old: float, new: float, tol: float) -> bool:
    if op == "<=":   # smaller is better
        return new > old * (1.0 + tol) and new > old + 1e-9
    return new < old * (1.0 - tol) and new < old - 1e-9


def _time_like(metric: str) -> bool:
    """Metrics whose VALUE is a function of the hardware the run measured
    on (throughput, wall-clock, overhead ratios of wall-clocks) — a
    cpu-run vs tpu-run diff of these is a hardware comparison, not a code
    regression. Proof counters and invariants (lost_pods, dispatches,
    *_bit_equal, e2e_recorded, ...) are NOT time-like: those must hold on
    every backend, so they gate across backends too."""
    return metric == "pods_per_sec" or metric.endswith(
        ("_ms", "_seconds", "_s", "_pct", "_per_sec", "_speedup"))


def compare(old_stages, new_stages, tol: float):
    """(delta lines, regression strings)."""
    lines, regressions = [], []
    for key in sorted(new_stages, key=str):
        new = new_stages[key]
        old = old_stages.get(key)
        kind, nodes, pods = key
        tag = f"{kind} {nodes}x{pods}"
        if old is None:
            lines.append(f"{tag}: NEW stage (no prior run)")
            continue
        # backend-aware gating: when the two runs measured on different
        # backends, time-like deltas are annotated and NOT gated
        ob, nb = old.get("backend"), new.get("backend")
        cross = bool(ob and nb and ob != nb)
        if cross:
            lines.append(f"{tag}: [cross-backend {ob}->{nb}] time-like "
                         f"metrics informational; invariants still gate")
        checked = {"pods_per_sec": ">=", "cycle_seconds": "<="}
        checked.update(_budget_metrics(kind, nodes))
        for metric, op in sorted(checked.items()):
            ov, nv = old.get(metric), new.get(metric)
            if not isinstance(ov, (int, float)) \
                    or not isinstance(nv, (int, float)):
                continue
            pct = ((nv - ov) / ov * 100.0) if ov else 0.0
            mark = ""
            # cycle_seconds drift is informational (the absolute budget in
            # bench.py is the enforced bound); budget metrics gate
            if metric != "cycle_seconds" and _regressed(op, ov, nv, tol):
                if cross and _time_like(metric):
                    mark = f"  [cross-backend {ob}->{nb}, not gated]"
                else:
                    mark = "  <-- REGRESSION"
                    regressions.append(
                        f"{tag} {metric}: {ov} -> {nv} ({pct:+.1f}%, "
                        f"op {op}, tolerance {tol:.0%})")
            lines.append(f"{tag}: {metric} {ov} -> {nv} ({pct:+.1f}%){mark}")
    for key in sorted(set(old_stages) - set(new_stages), key=str):
        kind, nodes, pods = key
        lines.append(f"{kind} {nodes}x{pods}: DROPPED (ran before, not now)")
    return lines, regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="directory holding BENCH_rNN.json")
    ap.add_argument("--tolerance", type=float,
                    default=float(os.environ.get("BENCH_TREND_TOLERANCE",
                                                 "0.25")),
                    help="fractional regression tolerance (default 0.25)")
    args = ap.parse_args(argv)

    paths = find_artifacts(args.dir)
    usable = [(p, load_stages(p)) for p in paths]
    usable = [(p, s) for p, s in usable if s]
    if len(usable) < 2:
        print(f"bench-trend: need two parseable BENCH_rNN.json artifacts "
              f"under {args.dir} (found {len(usable)}) — nothing to diff")
        return 0
    (old_path, old_stages), (new_path, new_stages) = usable[-2], usable[-1]
    print(f"bench-trend: {os.path.basename(old_path)} -> "
          f"{os.path.basename(new_path)} (tolerance {args.tolerance:.0%})")
    lines, regressions = compare(old_stages, new_stages, args.tolerance)
    for ln in lines:
        print("  " + ln)
    if regressions:
        print(f"bench-trend: {len(regressions)} budget-metric "
              f"regression(s):")
        for r in regressions:
            print("  " + r)
        return 1
    print("bench-trend: no budget-metric regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
