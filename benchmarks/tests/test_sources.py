"""The readers of what PR 24 put on the flight recorder's wave record
(`loop`, `children`, `waits`, `assumed_outstanding`), on hand-made records:
with the fields, without them (a parent commit records none: the reader
returns nothing, does not raise, and the metric is left out of the line),
and each metric file of theirs through `compute_metrics`."""

import json
import os

import pytest

from benchmarks.harness import cell, stats
from benchmarks.harness.sources import children, loop, waits

BC = "bind-commit/bind-call"


def wave(t_start, duration_s, bind_commit, loop_rec=None, ch=None, w=None,
         outstanding=None):
    rec = {"t_start": t_start, "duration_s": duration_s,
           "phases": [("pop", 0.01), ("bind-commit", bind_commit)],
           "stats": {"attempted": 10}}
    if loop_rec is not None:
        rec["loop"] = loop_rec
    if ch is not None:
        rec["children"] = ch
    if w is not None:
        rec["waits"] = w
    if outstanding is not None:
        rec["assumed_outstanding"] = outstanding
    return rec


def new_records():
    """Three waves of a 40 s window that opened at t = 100: the first wave's
    `loop` began at 70 (before the window), the others inside it."""
    return [
        wave(101.0, 2.0, 1.0,
             {"t_start": 70.0, "phases": [["idle-wait", 30.0],
                                          ["batch-wait", 0.15]],
              "handlers": {"calls": 5, "wait_s": 9.0, "held_s": 0.5}},
             {BC: [100, 0.6, 0.01], BC + "/apiserver.bind": [100, 0.5, 0.01],
              BC + "/apiserver.bind/store.txn": [100, 0.3, 0.005],
              "bind-commit/assume": [100, 0.1, 0.001]},
             {"queue": [100, 40.0, 0.9], "confirm": [0, 0.0, 0.0]}, 0),
        wave(110.0, 3.0, 2.0,
             {"t_start": 103.0, "phases": [["post-wave", 0.5],
                                           ["lock-wait", 2.0],
                                           ["idle-wait", 4.35],
                                           ["batch-wait", 0.15]],
              "handlers": {"calls": 200, "wait_s": 1.5, "held_s": 0.25}},
             {BC: [300, 1.5, 0.02], BC + "/apiserver.bind": [300, 1.2, 0.01],
              BC + "/apiserver.bind/store.txn": [300, 0.9, 0.005]},
             {"queue": [300, 60.0, 0.4], "confirm": [100, 250.0, 6.0]}, 100),
        wave(139.0, 0.5, 0.0,
             {"t_start": 113.0, "phases": [["post-wave", 0.25],
                                           ["lock-wait", 0.75],
                                           ["idle-wait", 24.85],
                                           ["batch-wait", 0.15]],
              "handlers": {"calls": 300, "wait_s": 0.5, "held_s": 0.75}},
             None,
             {"queue": [0, 0.0, 0.0], "confirm": [300, 150.0, 2.0]}, 7),
    ]


def old_records():
    """What the parent commit's recorder rings: none of the new fields."""
    return [wave(101.0, 2.0, 1.0), wave(110.0, 3.0, 2.0)]


def obs_of(waves, bound=400):
    return {"waves": waves, "window_s": 40.0, "bound_in_window": bound,
            "series": {}, "memory": {}, "trace": None}


def test_loop_phase_sums_leave_out_what_began_before_the_window():
    obs = obs_of(new_records())
    # the window opened no earlier than 139 - 40 = 99: wave 1's loop (70)
    # is left out, the other two count
    assert loop.read(obs, {"kind": "loop", "phase": "idle-wait"}) == \
        pytest.approx([4.35, 24.85])
    assert loop.read(obs, {"kind": "loop", "phase": "lock-wait"}) == \
        pytest.approx([2.0, 0.75])
    assert loop.read(obs, {"kind": "loop", "phase": "standby"}) == [0, 0]
    assert stats.reduce("sum", loop.read(
        obs, {"kind": "loop", "phase": "batch-wait"}), {}) == \
        pytest.approx(0.3)


def test_loop_of_a_backlog_window_counts_from_the_servers_start():
    waves = new_records()
    waves[0]["loop"]["t_start"] = 100.0   # the window opens at start()
    assert loop.read(obs_of(waves), {"kind": "loop", "phase": "idle-wait"}) \
        == pytest.approx([30.0, 4.35, 24.85])


def test_loop_handlers():
    obs = obs_of(new_records())
    assert loop.read(obs, {"kind": "loop", "handlers": "wait_s"}) == \
        pytest.approx([1.5, 0.5])
    assert loop.read(obs, {"kind": "loop", "handlers": "calls"}) == \
        [200, 300]


def test_loop_phases_of_a_wave_sum_to_the_gap_before_it():
    a, b, _c = new_records()
    gap = b["t_start"] - (a["t_start"] + a["duration_s"])
    assert sum(s for _, s in b["loop"]["phases"]) == pytest.approx(gap)
    assert b["loop"]["t_start"] == a["t_start"] + a["duration_s"]


def test_children_totals_and_the_phases_own_time():
    obs = obs_of(new_records())
    assert children.read(obs, {"kind": "children", "path": BC}) == \
        pytest.approx([0.6, 1.5])
    assert children.read(obs, {"kind": "children",
                               "path": BC + "/apiserver.bind/store.txn"}) \
        == pytest.approx([0.3, 0.9])
    # bind-commit's seconds less bind-call's: the commit loop's own time
    assert children.read(obs, {"kind": "children", "phase": "bind-commit",
                               "less": BC}) == pytest.approx([0.4, 0.5])
    assert children.read(obs, {"kind": "children", "path": "no/such"}) \
        is None
    per_pod = stats.reduce("per_bound_pod", children.read(
        obs, {"kind": "children", "path": BC}), {"bound_in_window": 400})
    assert per_pod * 1000 == pytest.approx(5.25)


def test_waits_are_means_weighted_by_count():
    obs = obs_of(new_records())
    assert waits.read(obs, {"kind": "waits", "wait": "queue"}) == \
        pytest.approx(100.0 / 400)
    assert waits.read(obs, {"kind": "waits", "wait": "confirm"}) == \
        pytest.approx(400.0 / 400)
    assert waits.read(obs, {"kind": "waits",
                            "field": "assumed_outstanding"}) == [0, 100, 7]
    none_yet = obs_of([wave(1.0, 1.0, 0.5, w={"queue": [0, 0.0, 0.0],
                                             "confirm": [0, 0.0, 0.0]})])
    assert waits.read(none_yet, {"kind": "waits", "wait": "confirm"}) is None


@pytest.mark.parametrize("reader, spec", [
    (loop, {"kind": "loop", "phase": "batch-wait"}),
    (loop, {"kind": "loop", "handlers": "held_s"}),
    (children, {"kind": "children", "path": BC}),
    (children, {"kind": "children", "phase": "bind-commit", "less": BC}),
    (waits, {"kind": "waits", "wait": "queue"}),
    (waits, {"kind": "waits", "wait": "confirm"}),
    (waits, {"kind": "waits", "field": "assumed_outstanding"}),
])
def test_records_without_the_new_fields_give_nothing(reader, spec):
    assert reader.read(obs_of(old_records()), spec) is None
    assert reader.read(obs_of([]), spec) is None


NEW = ["loop_batch_wait_s", "loop_idle_wait_s", "loop_lock_wait_s",
       "loop_post_wave_s",
       "handler_lock_wait_s", "handler_held_s", "bind_call_ms_per_pod"]
BACKLOG_ONLY = ["apiserver_bind_ms_per_pod", "store_txn_ms_per_pod",
                "commit_self_ms_per_pod", "confirm_lag_mean_s",
                "assumed_outstanding_max"]


def bench():
    with open(os.path.join(cell.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_metric_of_the_table_is_reported_for_its_cell():
    b = bench()
    out = cell.compute_metrics(b, "per_layer", "flagship-5k.backlog",
                               {**obs_of(new_records()), "rehearse": True})
    for name in [n + ".backlog" for n in NEW] + BACKLOG_ONLY:
        assert name in out, name
    assert out["store_txn_ms_per_pod"]["value"] == pytest.approx(3.0)
    assert out["commit_self_ms_per_pod"]["value"] == pytest.approx(2.25)
    assert out["confirm_lag_mean_s"]["value"] == pytest.approx(1.0)
    assert out["assumed_outstanding_max"] == {"value": 100.0, "unit": "pods"}
    assert out["loop_idle_wait_s.backlog"]["value"] == pytest.approx(29.2)
    out = cell.compute_metrics(b, "per_layer", "flagship-5k.arrivals",
                               {**obs_of(new_records()), "rehearse": True})
    for name in [n + ".arrivals" for n in NEW] + ["queue_wait_mean_ms"]:
        assert name in out, name
    assert out["queue_wait_mean_ms"]["value"] == pytest.approx(250.0)
    assert not set(BACKLOG_ONLY) & set(out)


def test_a_parents_records_leave_the_new_metrics_out_and_keep_the_old():
    b = bench()
    out = cell.compute_metrics(b, "per_layer", "flagship-5k.backlog",
                               {**obs_of(old_records()), "rehearse": True})
    assert "bind_commit_ms_per_pod" in out and "wave_gap_s" in out
    new = {m["name"] for m in b["per_layer"][21:]}
    assert len(new) == 20 and not new & set(out)


def test_each_new_entry_has_its_file_and_an_accepted_layer_and_target():
    b = bench()
    layers = {m["layer"] for m in b["per_layer"][:21]}
    for m in b["per_layer"][21:]:
        spec = cell.load_json(cell.BENCH_DIR, "metrics", m["name"] + ".json")
        assert spec["name"] == m["name"] and spec["layer"] == m["layer"]
        assert m["layer"] in layers
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        (cell_name,) = m["workloads"]
        e2e = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        assert cell_name in e2e["workloads"]
