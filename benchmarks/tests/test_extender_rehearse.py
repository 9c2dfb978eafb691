"""The extender cell's rehearsal on the CPU (64 nodes, 1,600 pods bound, 200
pending, the stand-in over real HTTP on loopback) ends in one valid last
line; with the extender's required (anti-)affinity dropped, or with `bind`
not assuming and the echo held back, it comes out not correct. Each run
compiles the three verbs' programs at the rehearsal size."""

import json

import pytest

from benchmarks.harness import cell
from benchmarks.tests import controls_extender
from benchmarks.tests.test_rehearse import BENCH, run_cli

CELL = "extender-5k.filter-prioritize"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_extender_rehearsal_ends_in_one_valid_line(trace):
    p = run_cli("--workload", CELL, "--seed", str(2 ** 31 + 17 + trace),
                "--seconds", "20", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 200
    assert last["device"]["platform"] == "cpu"
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in cell.metrics_of(BENCH, section, CELL)}
    # a CPU has no published peaks: no roofline share is reported
    assert set(last["metrics"]) == want - {"extender_engine_roofline_pct"}
    if trace:
        # 17 of the 50 groups carry host anti-affinity: their filter is
        # refused somewhere and asks for the reasons, a third dispatch
        assert last["metrics"]["extender_dispatches_per_pod"]["value"] \
            == pytest.approx(2.34)
    for name in ("filter_answers_wrong", "prioritize_answers_malformed",
                 "extender_call_errors", "bindings_infeasible_at_their_turn"):
        assert last["checks"][name] == {"value": 0, "limit": 0}
    assert "check compilations_in_window: 0 (limit 0) ok" in p.stdout
    assert "check bindings_replayed: 20 of 200 sent (extender_answers)" \
        in p.stdout


@pytest.mark.parametrize("control, must_fail", [
    ("ignore_required_affinity", ("filter_answers_wrong",
                                  "bindings_infeasible_at_their_turn")),
    ("no_assume_on_bind", ("filter_answers_wrong",)),
])
def test_a_broken_extender_is_not_correct(control, must_fail, monkeypatch):
    import kubernetes_tpu.extender.backend as backend
    import kubernetes_tpu.extender.served as served

    for mod in (backend, served):
        monkeypatch.setattr(mod, "pod_from_v1", mod.pod_from_v1)  # restored
    _code, result = cell.run_cell(
        CELL, 2 ** 31 + 99, 20.0, False, rehearse=True,
        sabotage=controls_extender.CONTROLS[control])
    assert result["correct"] is False
    for name in must_fail:
        assert result["checks"][name]["value"] > 0, name
    assert result["checks"]["extender_call_errors"]["value"] == 0
    assert result["checks"]["pods_never_bound"]["value"] == 0
