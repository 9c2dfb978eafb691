"""The gang cell's rehearsal on the CPU (128 nodes, one complete and one
incomplete job a shape, one oversized job a size) ends in one valid last
line; with the pods' group annotations dropped underneath it comes out not
correct, by `gangs_partly_bound`. Each run compiles at the rehearsal size."""

import json

import pytest

from benchmarks.harness import cell
from benchmarks.tests import controls_gang
from benchmarks.tests.test_rehearse import BENCH, run_cli

CELL = "gang-5k.backlog"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_gang_rehearsal_ends_in_one_valid_line(trace):
    p = run_cli("--workload", CELL, "--seed", str(2 ** 31 + 17 + trace),
                "--seconds", "20", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 480   # the complete jobs' pods, no other
    assert last["device"]["platform"] == "cpu"
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in cell.metrics_of(BENCH, section, CELL)}
    # a CPU has no published peaks: no roofline share is reported
    assert set(last["metrics"]) == want - {"gang_engine_roofline_pct"}
    if trace:
        # the first fixpoint, the oversized jobs with one incomplete job,
        # three incomplete jobs singly, the other twelve in bulk
        assert last["metrics"]["gang_rounds_first"]["value"] == 6
        assert last["metrics"]["gang_groups_rejected_first"]["value"] == 20
    assert last["checks"]["gangs_partly_bound"] == {"value": 0, "limit": 0}
    assert "check compilations_in_window: 0 (limit 0) ok" in p.stdout


def test_a_scheduler_that_ignores_gangs_is_not_correct(capfd, monkeypatch):
    import kubernetes_tpu.sched.server as srv

    monkeypatch.setattr(srv, "pod_from_v1", srv.pod_from_v1)   # restored
    real = cell.find_cell

    def quick(bench, name):   # nothing more lands: a short settle
        c, cfg, tr = real(bench, name)
        return c, cfg, {**tr, "settle_s": 5}

    monkeypatch.setattr(cell, "find_cell", quick)
    _code, result = cell.run_cell(CELL, 2 ** 31 + 99, 20.0, False,
                                  rehearse=True,
                                  sabotage=controls_gang.ignore_gangs)
    assert result["correct"] is False
    # every incomplete job has members bound and cannot reach min-available
    assert result["checks"]["gangs_partly_bound"]["value"] == 16
    assert "check gangs_partly_bound: 16 (limit 0) FAILED" \
        in capfd.readouterr().out
