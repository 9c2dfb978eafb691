"""A 64-node rehearsal of each cell on the CPU ends in one valid last line;
the same run with the timed path broken underneath comes out not correct.
Each run compiles at the rehearsal size (a minute or two on a cold cache)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import cell
from benchmarks.tests import controls

ROOT = cell.ROOT
BENCH = cell.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


def run_cli(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_ends_in_one_valid_line(workload, trace):
    p = run_cli("--workload", workload, "--seed", str(2 ** 31 + 7 + trace),
                "--seconds", "12", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"   # named for what it is
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in cell.metrics_of(BENCH, section, workload)}
    # on a CPU there are no published peaks: the roofline reader finds
    # nothing to read and the harness leaves the metric out
    assert set(last["metrics"]) == want - {"engine_roofline_pct"}
    assert all(isinstance(m["value"], float) for m in
               last["metrics"].values())
    if trace:
        assert last["device"]["busy_s"] > 0
        assert last["device"]["window_s"] > last["device"]["busy_s"]
        assert len(last["breakdown"]["device_ops"]) <= 10
    else:
        assert "setup_s" in last["metrics"]
    assert "check compilations_in_window: 0 (limit 0) ok" in p.stdout


def test_no_chip_no_result():
    p = run_cli("--workload", CELLS[0], "--seed", "1", "--seconds", "5",
                "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


@pytest.mark.parametrize("workload,control,check", [
    ("flagship-5k.backlog", "drop_bindings", "pods_never_bound"),
    ("flagship-5k.backlog", "ignore_required_affinity",
     "bindings_infeasible_at_their_turn"),
    ("flagship-5k.arrivals", "drop_bindings", "pods_never_bound"),
])
def test_a_broken_timed_path_is_not_correct(workload, control, check, capfd,
                                            monkeypatch):
    import kubernetes_tpu.sched.server as srv

    monkeypatch.setattr(srv, "pod_from_v1", srv.pod_from_v1)   # restored
    tr_settle = 5   # a lost pod never lands: do not wait the full settle
    real = cell.find_cell

    def quick(bench, name):
        c, cfg, tr = real(bench, name)
        return c, cfg, {**tr, "settle_s": tr_settle}

    monkeypatch.setattr(cell, "find_cell", quick)
    code, result = cell.run_cell(workload, 2 ** 31 + 99, 12.0, False,
                                 rehearse=True,
                                 sabotage=controls.CONTROLS[control])
    out = capfd.readouterr().out
    assert result["correct"] is False
    assert f"check {check}: 0 " not in out and f"check {check}: " in out
