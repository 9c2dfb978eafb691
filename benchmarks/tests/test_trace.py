"""The trace reducer, on a hand-made trace whose answers are known and on
the small recorded trace kept beside this file (a `--trace 1` run of
flagship-5k.backlog on a TPU v5 lite, cut to its first waves)."""

import json
import os

import pytest

from benchmarks.harness import roofline, trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def synthetic():
    ops = [["fusion.1", 100 * MS, 50 * MS],      # 100-150
           ["fusion.2", 150 * MS, 20 * MS],      # follows: 150-170
           ["copy.3", 400 * MS, 100 * MS],       # 400-500
           ["fusion.1", 900 * MS, 200 * MS]]     # 900-1100, cut at 1000
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            [trace.MARK_OPEN, 0, 10], ["noise", 5, 5],
            [trace.MARK_CLOSE, 1000 * MS, 10]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_x", 100 * MS, 900 * MS]]},
            {"name": "XLA Ops", "events": ops}]}]}


def test_busy_idle_and_breakdown_on_a_known_trace():
    # the harness set the marks at perf_counter 50.0 and 51.0; one wave ran
    # 50.1 .. 50.7: snapshot 0.05, dispatch 0.2, bind-commit 0.35
    waves = [{"t_start": 50.1, "phases": [("snapshot", 0.05),
                                          ("dispatch", 0.2),
                                          ("bind-commit", 0.35)]}]
    red = trace.reduce_trace(synthetic(), 50.0, 51.0, waves)
    assert red["window_s"] == pytest.approx(1.0)
    assert red["busy_s"] == pytest.approx(0.07 + 0.1 + 0.1)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.15)]
    assert dict(red["device_ops"])["copy.3"] == pytest.approx(0.1)
    gaps = dict(red["idle_gaps"])
    # idle: 0-100, 170-400, 500-900 ms. The wave covers 100-700 ms:
    # snapshot 100-150 (busy), dispatch 150-350 -> idle 170-350,
    # bind-commit 350-700 -> idle 350-400 and 500-700
    assert gaps["dispatch"] == pytest.approx(0.18)
    assert gaps["bind-commit"] == pytest.approx(0.05 + 0.2)
    assert gaps["between-waves"] == pytest.approx(0.1 + 0.2)
    assert sum(gaps.values()) == pytest.approx(1.0 - red["busy_s"])


def test_a_loop_is_its_childrens_time_and_names_are_cut():
    t = synthetic()
    t["planes"][1]["lines"][1]["events"].append(
        ["while.9", 90 * MS, 100 * MS])       # holds 100-150 and 150-170
    red = trace.reduce_trace(t, 50.0, 51.0, [])
    assert "while.9" not in dict(red["device_ops"])
    assert red["busy_s"] == pytest.approx(0.1 + 0.1 + 0.1)   # 90-190
    assert trace.short_name(
        "%fusion.64 = s32[2949696]{0:T(1024)S(1)} fusion(s32[37748736]{0} "
        "%bitcast.242), kind=kCustom") == "fusion.64 s32[2949696]"
    assert trace.short_name("%while.2 = (s32[]{:T(128)}, s32[8]{0}) "
                            "while(...)") == "while.2 (s32[]"
    assert trace.short_name("copy.3") == "copy.3"


def test_a_trace_without_marks_or_device_is_an_error():
    t = synthetic()
    t["planes"][0]["lines"][0]["events"] = []
    with pytest.raises(ValueError, match="marks"):
        trace.reduce_trace(t, 0.0, 1.0, [])
    t = synthetic()
    t["planes"].pop()
    with pytest.raises(ValueError, match="no device operations"):
        trace.reduce_trace(t, 0.0, 1.0, [])


def test_recorded_trace():
    path = os.path.join(HERE, "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    red = trace.reduce_trace(rec["trace"], rec["t_open"], rec["t_close"],
                             rec["waves"])
    want = rec["expect"]
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert [n for n, _ in red["device_ops"]] == want["top_ops"]
    idle = red["window_s"] - red["busy_s"]
    assert sum(s for _, s in red["idle_gaps"]) <= idle * (1 + 1e-9)
    assert red["idle_gaps"][0][0] == want["longest_gap"]


def test_roofline_needs_a_known_device():
    dims = {"N": 5120, "P": 53248, "E": 65536, "R": 4, "L": 8, "K": 4,
            "SC": 64}
    assert roofline.cycle_bytes(dims) == (
        5120 * 20 * 4 + 65536 * 12 + 53248 * 16 + 64 * 5120 * 5)
    pct = roofline.roofline_pct(dims, cycles=10, busy_seconds=3.7,
                                device_kind="TPU v5 lite")
    assert 0 < pct < 1
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.roofline_pct(dims, 10, 3.7, "TPU v9 imaginary")
