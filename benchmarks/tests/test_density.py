"""The cell `density-1k.backlog` (ISSUE 26): its 64-node rehearsal on the CPU
ends in one valid line that drained in ONE wave without a relist, the three
watch-plane metrics resolve through their files (and are absent, not zero,
on a parent's records), and the cell's control is caught (the control is
`chip_control.py`'s `drop_bindings` on this cell). The rehearsals
compile at the 64-node size (a minute or two on a cold cache)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import cell

ROOT = cell.ROOT
BENCH = cell.load_json(ROOT, "BENCHMARK.json")
CELL = "density-1k.backlog"
NEW = {"pump_lag_max_events": ("pump_lag_max", "max"),
       "watch_evictions": ("watch_evictions", "sum"),
       "informer_relists": ("informer_relists", "sum")}


def run_cli(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)


def test_the_configuration_is_the_published_one_cut_nowhere():
    conf = next(c for c in BENCH["configs"] if c["name"] == "density-1k")
    cfg = cell.load_json(ROOT, conf["file"])
    assert conf["reduced"] == [] and cfg["reduced"] == {}
    assert (cfg["nodes"], cfg["backlog_pods"], cfg["published_backlog_pods"],
            cfg["groups"], cfg["roles"]) == (1000, 30000, 30000, 50,
                                             {"plain": 50})
    # upstream's own shapes (scheduler_test.go baseNodeTemplate, test/utils
    # MakePodSpec): 4 CPU / 32Gi / 110-pod nodes, ONE pod shape of 100m /
    # 500Mi, so that 30 pods a node fill 3 of its 4 CPU
    assert (cfg["node_cpu"], cfg["node_memory"], cfg["node_pods"]) == (
        "4000m", f"{32 * 1024 * 1024}Ki", 110)
    assert cfg["request_tiers"] == [["100m", f"{500 * 1024}Ki"]]
    assert not {"node_shape", "request_tiers"} & set(cfg["assumed"])
    w = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "density-1k", "restart-backlog", 1)
    # the cell reports the drain rate, set-up and, traced, the new metrics
    assert {m["name"] for m in cell.metrics_of(BENCH, "end_to_end", CELL)} \
        == {"drain_pods_per_s", "setup_s"}
    assert set(NEW) <= {m["name"] for m in
                        cell.metrics_of(BENCH, "per_layer", CELL)}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_drains_in_one_wave_without_a_relist(trace):
    p = run_cli("--workload", CELL, "--seed", str(2 ** 31 + 611 + trace),
                "--seconds", "12", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    info = json.loads(next(ln for ln in lines if ln.startswith("info "))[5:])
    assert last["correct"] is True and last["failed"] == 0
    # 64 nodes x 1,900 pods: upstream's 30 pods a node
    assert last["attempted"] == 1900 == info["bound_in_window"]
    assert info["n_waves"] == 1 and info["waves"][0]["scheduled"] == 1900
    # the floor: the pod and the node informer's initial lists
    assert info["informer_relists_in_window"] == 2
    assert info["watch_restarts"] == 0
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in cell.metrics_of(BENCH, section, CELL)}
    assert set(last["metrics"]) == want
    if trace:
        assert last["metrics"]["watch_evictions"]["value"] == 0.0
        assert last["metrics"]["informer_relists"]["value"] == 0.0
        assert last["metrics"]["pump_lag_max_events"]["value"] >= 0.0


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_files_resolve_and_a_parents_records_leave_them_out(name):
    field, how = NEW[name]
    spec = cell.load_json(cell.BENCH_DIR, "metrics", name + ".json")
    assert spec["name"] == name
    assert spec["source"] == {"kind": "waits", "field": field}
    assert spec["reduce"] == how
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["flagship-5k.backlog", CELL]
    assert entry["moves"] == "drain_pods_per_s"
    assert entry["layer"] == spec["layer"]
    base = {"t_start": 1.0, "duration_s": 2.0, "stats": {"attempted": 10},
            "phases": [("bind-commit", 1.0)]}
    obs = {"bound_in_window": 20, "window_s": 10.0, "series": {},
           "memory": {}, "trace": None, "waves": [
               {**base, "pump_lag_max": 7, "watch_evictions": 1,
                "informer_relists": 0},
               {**base, "pump_lag_max": 40, "watch_evictions": 0,
                "informer_relists": 2}]}
    only = {"per_layer": [entry]}
    got = cell.compute_metrics(only, "per_layer", CELL, obs)
    assert got[name]["value"] == {"pump_lag_max_events": 40.0,
                                  "watch_evictions": 1.0,
                                  "informer_relists": 2.0}[name]
    # a parent's records have no such field: absent, not zero
    obs["waves"] = [base, base]
    assert cell.compute_metrics(only, "per_layer", CELL, obs) == {}


def test_the_cells_control_is_caught():
    """`chip_control.py --workload density-1k.backlog --control
    drop_bindings` is the cell's control on the chip (3 seeds there)."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tests",
                                      "chip_control.py"),
         "--workload", CELL, "--control", "drop_bindings", "--seeds",
         str(2 ** 31 + 731), "--seconds", "12", "--rehearse"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[-1])["not_correct"] == 1
    lost = next(ln for ln in lines if ln.startswith("check pods_never_bound"))
    assert int(lost.split()[2]) > 0 and lost.endswith("FAILED")
