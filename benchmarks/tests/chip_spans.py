#!/usr/bin/env python3
"""One traced run of a cell, in this process, that keeps what the result
line reduces away: the flight recorder's wave records of the window go to
`chiprun_out/spans_<workload>_<seed>.json`, and the checks ISSUE 24 states on
them are printed — for every pair of consecutive waves the `loop` phases
against the gap between the two spans (limit 5 ms); per wave the parts of a
Binding against the `bind-commit` phase and their nesting; the Bindings the
scheduler timed against those the apiserver served. Then the window's sums:
loop phases by name beside the run's `breakdown.idle_gaps`, the handlers'
account, every child path, the waits. The run's result is the last line, as
`run.py` prints it. Records without the new fields are reported as such.

    python3 benchmarks/tests/chip_spans.py --workload flagship-5k.backlog \
        --seed 3000000019 --seconds 40 [--rehearse]
"""

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def check_waves(waves: list) -> dict:
    """The record-level criteria, as numbers: the worst |loop sum - gap|, the
    waves whose Binding parts exceed their phase or nest out of order."""
    worst_gap, over, disorder = 0.0, 0, 0
    for a, b in zip(waves, waves[1:]):
        if b.get("loop"):
            gap = b["t_start"] - (a["t_start"] + a["duration_s"])
            worst_gap = max(worst_gap, abs(
                sum(s for _, s in b["loop"]["phases"]) - gap))
    for w in waves:
        ch = w.get("children") or {}
        phase = sum(d for n, d in w["phases"] if n == "bind-commit")

        def total(path):
            return ch.get(path, [0, 0.0, 0.0])[1]

        bc = "bind-commit/bind-call"
        parts = total("bind-commit/assume") + total(bc) \
            + total("bind-commit/finish")
        over += parts > phase + 1e-5
        disorder += not (total(bc + "/apiserver.bind/store.txn")
                         <= total(bc + "/apiserver.bind") + 1e-6
                         and total(bc + "/apiserver.bind")
                         <= total(bc) + 1e-6)
    return {"loop_sum_vs_gap_worst_s": round(worst_gap, 6),
            "waves_whose_binding_parts_exceed_the_phase": over,
            "waves_nested_out_of_order": disorder}


def sums(waves: list) -> dict:
    loop, handlers, children, waits = {}, {}, {}, {}
    for w in waves:
        for name, s in (w.get("loop") or {}).get("phases", []):
            loop[name] = loop.get(name, 0.0) + s
        for k, v in ((w.get("loop") or {}).get("handlers") or {}).items():
            handlers[k] = handlers.get(k, 0) + v
        for path, (n, s, m) in (w.get("children") or {}).items():
            c = children.setdefault(path, [0, 0.0, 0.0])
            c[0] += n
            c[1] += s
            c[2] = max(c[2], m)
        for k, (n, s, m) in (w.get("waits") or {}).items():
            c = waits.setdefault(k, [0, 0.0, 0.0])
            c[0] += n
            c[1] += s
            c[2] = max(c[2], m)
    phases = {}
    for w in waves:
        for name, d in w["phases"]:
            phases[name] = phases.get(name, 0.0) + d

    def rnd(d):
        return {k: (round(v, 4) if isinstance(v, float)
                    else [v[0], round(v[1], 4), round(v[2], 6)]
                    if isinstance(v, list) else v) for k, v in d.items()}

    return {"loop_s": rnd(loop), "handlers": rnd(handlers),
            "phases_s": rnd(phases), "children": rnd(children),
            "waits": rnd(waits),
            "assumed_outstanding": [w.get("assumed_outstanding")
                                    for w in waves][:8]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.harness import cell

    kept = {}
    inner = cell.compute_metrics

    def keeping(bench, section, workload, obs):
        kept["waves"] = obs["waves"]
        return inner(bench, section, workload, obs)

    cell.compute_metrics = keeping
    try:
        code, result = cell.run_cell(args.workload, args.seed, args.seconds,
                                     True, rehearse=args.rehearse,
                                     t_process=T_PROCESS)
    except cell.Deadline:
        print("stopped by its deadline or SIGTERM; no result",
              file=sys.stderr, flush=True)
        return 1
    waves = kept.get("waves", [])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"spans_{args.workload}_{args.seed}.json"), "w") as f:
        json.dump(waves, f)
    if not any(w.get("loop") or w.get("children") for w in waves):
        print("spans: these records carry none of the new fields",
              flush=True)
    else:
        from kubernetes_tpu.apiserver.server import REQUEST_DURATION
        from kubernetes_tpu.sched.metrics import BINDING_DURATION
        from kubernetes_tpu.storage.store import TXN_DURATION

        create = dict(verb="create", resource="pods", subresource="")
        print("spans " + json.dumps({
            "n_waves": len(waves), **check_waves(waves),
            "scheduler_binding_duration_seconds_count":
                BINDING_DURATION.count(),
            "bindings_the_apiserver_served": REQUEST_DURATION.count(
                verb="create", resource="pods", subresource="binding"),
            # the process's pod creates (set-up's too), off the wave's
            # thread: what the two newly fed metric families say of them
            "pod_creates": REQUEST_DURATION.count(**create),
            "pod_create_mean_ms": round(
                1000 * REQUEST_DURATION.sum_value(**create)
                / max(REQUEST_DURATION.count(**create), 1), 4),
            "store_create_txn_mean_ms": round(
                1000 * TXN_DURATION.sum_value(op="create")
                / max(TXN_DURATION.count(op="create"), 1), 4),
            **sums(waves),
            "idle_gaps": (result.get("breakdown") or {}).get("idle_gaps")}),
            flush=True)
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
