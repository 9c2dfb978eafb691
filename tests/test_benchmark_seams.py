"""The benchmark harness's seams without a run (seconds; no device, no
compile): the fast cases of benchmarks/tests/test_seams_fast.py, which ISSUE
31 asked for in tier-1, and what ISSUE 32 adds behind the seams: the
`gang_jobs` shapes, the `gangs` check, the `gang_backlog` kind, the gang
loop's roofline reader, and every per-layer entry's file."""

import collections
import os

import pytest

from benchmarks.harness import cell
from benchmarks.harness.checks import gangs
from benchmarks.harness.kinds import gang_backlog
from benchmarks.harness.shapes import gang_jobs
from benchmarks.harness.sources import gang_roofline
# collected here as this module's own (the defaults' case is restated below:
# there it runs over every cell, and a cell may now name its modules)
from benchmarks.tests.test_seams_fast import (  # noqa: F401
    test_an_unknown_name_ends_the_run_with_the_names_that_exist,
    test_burst_keeps_the_count_and_the_mean_rate,
    test_burst_sends_one_groups_pods_together_whatever_the_seed,
    test_serving_dims_are_todays_with_and_without_a_dims_block,
    test_sized_groups_add_up_and_do_not_depend_on_the_seed,
    test_sized_groups_refuse_sizes_that_are_not_the_configurations)

BENCH = cell.load_json(cell.ROOT, "BENCHMARK.json")
GANG = cell.load_json(cell.ROOT, "benchmarks", "configs", "gang-5k.json")
GANG_CELL = "gang-5k.backlog"


@pytest.mark.parametrize("workload", [
    "flagship-5k.arrivals", "flagship-5k.backlog", "density-1k.backlog"])
def test_the_defaults_are_todays_modules(workload):
    _cell, cfg, tr = cell.find_cell(BENCH, workload)
    assert not {"pod_shapes", "checks", "wiring", "dims"} & set(cfg)
    plugs = cell.plug_ins(BENCH, "per_layer", workload, cfg, tr)
    assert plugs["shapes"].__name__.endswith("shapes.equal_groups")
    assert [n for n, _m in plugs["checks"]] == ["placement"]
    assert plugs["wiring"].__name__.endswith("wirings.local")
    assert plugs["kind"].__name__.endswith("kinds." + tr["kind"])


def test_the_gang_cell_names_its_modules_and_they_are_there():
    _cell, cfg, tr = cell.find_cell(BENCH, GANG_CELL)
    plugs = cell.plug_ins(BENCH, "per_layer", GANG_CELL, cfg, tr)
    assert plugs["shapes"] is gang_jobs and plugs["kind"] is gang_backlog
    assert [n for n, _m in plugs["checks"]] == ["placement", "gangs"]
    assert plugs["wiring"].__name__.endswith("wirings.local")
    from benchmarks.harness.wirings import local

    d = local.serving_dims(cfg)   # the PUBLISHED deployment's capacities
    assert (d.N, d.P, d.E, d.GR, d.SC) == (5120, 106496, 131072, 4096, 64)


def test_the_daemon_cell_names_its_modules_and_its_capacities():
    """ISSUE 49: `daemonset-5k.backlog` behind the seams: its four modules
    are there by name, the device program is provisioned for the 20,000
    waiting and the ~19,850 bound at the end, and NOTHING for a number of
    pinned pods: SC, SN, STL are `DEFAULT_DIMS`' and the Dims defaults."""
    from benchmarks.harness.wirings import local
    from kubernetes_tpu.state.dims import Dims

    name = "daemonset-5k.backlog"
    c, cfg, tr = cell.find_cell(BENCH, name)
    assert (c["traffic"], tr["kind"], c["chips"]) == (
        "daemonset-restart-backlog", "daemon_backlog", 1)
    plugs = cell.plug_ins(BENCH, "per_layer", name, cfg, tr)
    assert plugs["shapes"].__name__.endswith("shapes.daemon_pods")
    assert plugs["kind"].__name__.endswith("kinds.daemon_backlog")
    assert plugs["wiring"].__name__.endswith("wirings.local_daemons")
    assert [n for n, _m in plugs["checks"]] == ["placement", "daemons"]
    d = local.serving_dims(cfg)
    assert (d.N, d.P, d.E) == (5120, 20480, 32768) and "dims" not in cfg
    assert (d.SC, d.SN, d.STL, d.F) == (
        local.DEFAULT_DIMS["SC"], Dims().SN, Dims().STL, Dims().F)
    assert name in next(m for m in BENCH["end_to_end"]
                        if m["name"] == "drain_pods_per_s")["workloads"]


def test_the_binpack_cell_names_its_modules_and_its_capacities():
    """ISSUE 53: `gpu-binpack-5k.backlog` behind the seams: its modules are
    there by name, the device program is provisioned for the 9,780 waiting,
    the 19,280 bound at the end and a resource axis that holds the pool's
    extended resource; SC is `DEFAULT_DIMS`'."""
    from benchmarks.harness.wirings import local, local_policy

    name = "gpu-binpack-5k.backlog"
    c, cfg, tr = cell.find_cell(BENCH, name)
    # the whole four-chip host, for steadiness alone: the program uses one
    assert (c["traffic"], tr["kind"], c["chips"]) == (
        "gpu-restart-backlog", "pool_backlog", 4)
    plugs = cell.plug_ins(BENCH, "per_layer", name, cfg, tr)
    assert plugs["shapes"].__name__.endswith("shapes.gpu_pool")
    assert plugs["kind"].__name__.endswith("kinds.pool_backlog")
    assert plugs["wiring"] is local_policy
    assert [n for n, _m in plugs["checks"]] == ["placement", "accelerators"]
    d = local_policy.Cluster(cfg).dims
    assert (d.N, d.P, d.E, d.R) == (5120, 10240, 32768, 8)
    assert "dims" not in cfg and d.SC == local.DEFAULT_DIMS["SC"]
    assert name in next(m for m in BENCH["end_to_end"]
                        if m["name"] == "drain_pods_per_s")["workloads"]
    mine = [m["name"] for m in BENCH["per_layer"]
            if m.get("workloads") == [name]]
    assert mine == ["fill_pods_first", "fill_rounds_first",
                    "gpu_nodes_opened_over_reference", "rtc_score_resources",
                    "binpack_engine_roofline_pct"]
    kind = plugs["kind"].Kind(tr, cfg, 40.0)
    assert (kind.prebound, kind.work) == (9500, 9780)


def test_gang_jobs_are_the_same_table_whatever_the_seed():
    tables, names = set(), []
    for seed in (3, 2 ** 31 + 9):
        pop = gang_jobs.Population(GANG, seed, GANG["backlog_pods"])
        must = pop.pending(GANG["backlog_pods"], seed, "job")
        must_not = pop.waiting(seed, "job")
        assert (len(must), len(must_not), pop.n) == (29280, 1200, 16)
        jobs: dict = {}
        for p in must + must_not:
            meta, spec = p["metadata"], p["spec"]
            req = spec["containers"][0]["resources"]["requests"]
            jobs.setdefault(meta["annotations"][gang_jobs.GROUP], []).append(
                (pop.group_of(p), int(meta["annotations"][
                    gang_jobs.MIN_AVAILABLE]), spec["priority"], req["cpu"]))
        assert len(jobs) == 1024
        assert all(len(set(members)) == 1 for members in jobs.values())
        kinds = collections.Counter()
        for members in jobs.values():
            _shape, least, _prio, cpu = members[0]
            kinds["oversized" if cpu == "64000m" else
                  "complete" if len(members) == least else "incomplete",
                  len(members)] += 1
        assert kinds == {
            **{("complete", s): 244 for s in (8, 16, 32, 64)},
            **{("incomplete", s): 8 for s in (6, 12, 24, 48)},
            **{("oversized", s): 4 for s in (8, 16, 32, 64)}}
        # minMember is the job's size; 68.6 % of the cluster's 160,000 CPU
        assert {m[0][1] for m in jobs.values()} == {8, 16, 32, 64}
        assert sum(int(p["spec"]["containers"][0]["resources"]["requests"]
                       ["cpu"][:-1]) for p in must) == 109_800_000
        tables.add(tuple(sorted((m[0], len(m)) for m in jobs.values())))
        names.append({p["metadata"]["name"] for p in must + must_not})
        assert len(names[-1]) == 30480
        warm = collections.Counter(   # whole small jobs
            (p["metadata"]["annotations"][gang_jobs.GROUP],
             p["metadata"]["annotations"][gang_jobs.MIN_AVAILABLE])
            for p in pop.pending(128, seed, "warm0"))
        assert len(warm) == 16 and set(warm.values()) == {8}
        assert {least for _job, least in warm} == {"8"}
    assert len(tables) == 1 and names[0] != names[1]


def test_gang_jobs_refuse_counts_that_are_not_the_configurations():
    with pytest.raises(SystemExit) as e:
        gang_jobs.job_table({**GANG, "complete_jobs_per_shape": 57})
    assert "backlog_pods 29280" in str(e.value)


def _member(name, job, least, node=""):
    return {"metadata": {"name": name, "namespace": "default", "annotations": {
        gangs.GROUP: job, gangs.MIN_AVAILABLE: str(least)}},
        "spec": {"nodeName": node} if node else {}}


@pytest.mark.parametrize("bound, partly", [
    (0, 0), (1, 1), (3, 1), (4, 0), (5, 0)])
def test_the_gangs_check_finds_a_partly_bound_job(bound, partly):
    pods = [_member(f"a-{i}", "a", 4, "node-0" if i < bound else "")
            for i in range(5)]
    pods += [_member(f"b-{i}", "b", 2, "node-1") for i in range(2)]
    pods.append({"metadata": {"name": "plain"}, "spec": {"nodeName": "n"}})
    found = gangs.final_state([], pods, {})
    assert len(found) == partly
    assert all("default/a" in f and f"{bound} members" in f for f in found)


def test_an_incomplete_job_with_any_member_bound_is_partly_bound():
    pods = [_member(f"c-{i}", "c", 8, "node-0") for i in range(6)]
    assert len(gangs.final_state([], pods, {})) == 1


def test_the_gang_backlogs_work_is_the_complete_jobs_alone():
    class Resource:
        def __init__(self):
            self.made = []

        def create(self, obj):
            self.made.append(obj["metadata"]["name"])

    class Fake:
        def __init__(self):
            self.pods = Resource()
            self.client, self.stopped, self.adopted = self, 0, None
            self.prewarmer = None

        def stop(self):
            self.stopped += 1

        def new_server(self):
            return "fresh"

        def adopt_warmth(self, warm, fresh):
            self.adopted = (warm, fresh)

    fake, seed = Fake(), 2 ** 31 + 5
    kind = gang_backlog.Kind(
        cell.load_json(cell.BENCH_DIR, "traffic", "gang-restart-backlog.json"),
        GANG, 40.0)
    pop = gang_jobs.Population(GANG, seed, kind.work)
    server, pods = kind.prepare(fake, fake, None, pop, seed)
    assert server == "fresh" and fake.adopted == (fake, "fresh")
    assert (kind.prebound, kind.work, fake.stopped) == (0, 29280, 2)
    names = [p["metadata"]["name"] for p in pods]
    assert kind.names == names and len(set(names)) == 29280
    waiting = {p["metadata"]["name"] for p in pop.waiting(seed, "job")}
    assert not waiting & set(names)
    # all 30,480 are at the apiserver
    assert set(fake.pods.made) == waiting | set(names)
    assert len(fake.pods.made) == 30480


def test_the_gang_roofline_counts_a_cycles_bytes_once_a_fixpoint():
    from benchmarks.harness import roofline

    dims = {"N": 5120, "P": 106496, "E": 131072, "R": 4, "L": 8, "K": 4,
            "SC": 64}
    obs = {"trace": {"busy_s": 2.0, "window_s": 20.0}, "rehearse": False,
           "dims": dims, "device": {"kind": "TPU v5 lite"},
           "waves": [{"gang_rounds": 6}, {}, {"gang_rounds": 2}]}
    got = gang_roofline.read(obs, {"kind": "gang_roofline"})
    assert got == pytest.approx(
        100 * 8 * roofline.cycle_bytes(dims) / 819e9 / 2.0)
    assert 0 < got < 100
    # a program that records no rounds (the parent), a CPU, no trace
    assert gang_roofline.read({**obs, "waves": [{}]}, {}) is None
    assert gang_roofline.read({**obs, "rehearse": True}, {}) is None
    assert gang_roofline.read({**obs, "trace": None}, {}) is None


@pytest.mark.parametrize("entry", BENCH["per_layer"] + BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_every_metric_has_its_file_and_its_reader(entry):
    spec = cell.load_json(cell.BENCH_DIR, "metrics", entry["name"] + ".json")
    assert spec["name"] == entry["name"]
    if "layer" in entry:
        assert spec["layer"] == entry["layer"]
    assert os.path.isfile(os.path.join(
        cell.HARNESS_DIR, "sources", spec["source"]["kind"] + ".py"))
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells


# --------------------------------------------------------------------------- #
# ISSUE 34: the extender cell behind the seams: its wiring and kind TOGETHER,
# the stand-in's choice, the answers check, the roofline reader
# --------------------------------------------------------------------------- #

from benchmarks.harness import reference  # noqa: E402
from benchmarks.harness.checks import extender_answers  # noqa: E402
from benchmarks.harness.kinds import extender_loop  # noqa: E402
from benchmarks.harness.sources import extender_roofline  # noqa: E402

EXT = cell.load_json(cell.ROOT, "benchmarks", "configs", "extender-5k.json")
EXT_CELL = "extender-5k.filter-prioritize"


def test_the_extender_cell_names_its_modules_and_they_are_there():
    entry, cfg, tr = cell.find_cell(BENCH, EXT_CELL)
    assert (entry["chips"], entry["traffic"]) == (1, "scheduleone-backlog")
    plugs = cell.plug_ins(BENCH, "per_layer", EXT_CELL, cfg, tr)
    assert plugs["kind"] is extender_loop
    assert plugs["wiring"].__name__.endswith("wirings.extender")
    assert plugs["shapes"].__name__.endswith("shapes.equal_groups")
    assert [n for n, _m in plugs["checks"]] == ["placement",
                                                "extender_answers"]
    d = plugs["wiring"].serving_dims(cfg)
    assert (d.N, d.P, d.E, d.SC, d.SL) == (5120, 8, 65536, 64, 64)
    # the source's shapes are not cut: the flagship's cluster and groups,
    # its 50,000 pods bound, every node's name with every filter
    flagship = cell.load_json(cell.ROOT, "benchmarks", "configs",
                              "flagship-5k.json")
    for key in ("nodes", "zones", "racks_per_zone", "node_cpu",
                "node_memory", "node_pods", "groups", "roles",
                "request_tiers", "zone_spread", "existing_pods"):
        assert cfg[key] == flagship[key], key
    assert cfg["candidate_names_per_filter"] == cfg["nodes"] == 5000
    policy = cfg["extender_policy"]
    assert (policy["nodeCacheCapable"], policy["ignorable"],
            policy["httpTimeout_s"], policy["weight"]) == (True, False, 5, 1)
    assert cfg["backlog_pods"] % cfg["groups"] == 0 \
        and cfg["backlog_pods"] >= 100
    kind = extender_loop.Kind(tr, cfg, 40.0)
    assert (kind.prebound, kind.work, kind.check_spread) == (
        50000, 50000, True)
    assert extender_loop.KEPT.every == 10
    # what the cell reports: the drain rate, and the stand-in's own share
    assert EXT_CELL in next(m for m in BENCH["end_to_end"] if m["name"]
                            == "drain_pods_per_s")["workloads"]
    named = {m["name"] for m in cell.metrics_of(BENCH, "per_layer", EXT_CELL)}
    assert {"standin_self_ms_per_pod", "extender_dispatches_per_pod",
            "extender_engine_roofline_pct", "bind_commit_ms_per_pod",
            "device_idle_pct.backlog"} <= named


@pytest.mark.parametrize("field, want", [("eval_reused", 1.0), (None, None)])
def test_the_reuse_metric_reads_the_records_field_or_nothing(field, want):
    """`extender_eval_reused_per_pod` through its file: the records'
    `eval_reused` over the pods bound; a program that records no such field
    (the parent of ISSUE 52) leaves the metric out and nothing raises."""
    rec = {"t_start": 1.0, "duration_s": 0.05, "phases": [],
           "stats": {"attempted": 1}, "dispatches": 1}
    if field:
        rec[field] = 1
    obs = {"window_s": 40.0, "bound_in_window": 3, "series": {},
           "memory": {}, "trace": None, "rehearse": True,
           "waves": [dict(rec) for _ in range(3)]}
    out = cell.compute_metrics(BENCH, "per_layer", EXT_CELL, obs)
    assert out["extender_dispatches_per_pod"]["value"] == 1.0
    if want is None:
        assert "extender_eval_reused_per_pod" not in out
    else:
        assert out["extender_eval_reused_per_pod"] == {
            "value": want, "unit": "answers/pod"}
    # (by name: later PRs' entries go behind it, at the end of the list)
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == "extender_eval_reused_per_pod")
    assert entry["workloads"] == [EXT_CELL]
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "extender server", "drain_pods_per_s", "higher")


@pytest.mark.parametrize("answer, malformed, chosen", [
    ([("a", 3), ("b", 10), ("c", 10)], "", {"b", "c"}),
    ([("a", 3), ("b", 10)], "candidates unscored", {"b"}),
    ([("a", 3), ("b", 9), ("c", 2), ("b", 9)], "scored twice", {"b"}),
    ([("a", 3), ("b", 9), ("c", 2), ("d", 10)], "hosts not asked", {"b"}),
    ([("a", 11), ("b", 4), ("c", 2)], "scored 11", {"b"}),
    ([("a", 2.5), ("b", 1), ("c", 0)], "scored 2.5", {"b"}),
])
def test_the_stand_in_takes_the_highest_score_and_holds_answers_to_form(
        answer, malformed, chosen):
    kept = extender_loop.Kept()
    standin = extender_loop.StandIn(None, {"httpTimeout_s": 5})
    standin.reseed(3, kept)
    prio = [{"Host": h, "Score": s} for h, s in answer]
    hosts = {standin._select_host("p", ["a", "b", "c"], prio)
             for _ in range(16)}
    assert hosts == chosen    # ties: every one of them comes up, no other
    assert len(kept.malformed) == (16 if malformed else 0)
    assert all(malformed in m for m in kept.malformed)


def _world_with_one_anti_pod():
    cfg = {"nodes": 4, "zones": 2, "racks_per_zone": 1, "node_cpu": "4000m",
           "node_memory": "8388608Ki", "node_pods": 110, "groups": 1,
           "roles": {"anti": 1}, "zone_spread": False,
           "request_tiers": [["1000m", "1048576Ki"]]}
    from benchmarks.harness import objects

    groups = objects.Groups(cfg, 1, 4)
    nodes = objects.make_nodes(cfg)
    world = reference.World(nodes, [groups.pod(0, "shape")])
    world.add(groups.pod(0, "there", "node-2"), "node-2")
    return world, groups.pod(0, "asks"), [n["metadata"]["name"]
                                          for n in nodes]


@pytest.mark.parametrize("passed, failed, wrong", [
    (["node-0", "node-1", "node-3"], {"node-2": "anti-affinity"}, []),
    (["node-0", "node-1", "node-2", "node-3"], {},
     ["passed node-2, the reference refuses it"]),
    (["node-0", "node-3"], {"node-1": "?", "node-2": "anti-affinity"},
     ["refused node-1"]),
    (["node-0", "node-1", "node-3"], {},
     ["0 FailedNodes for 1 nodes not passed"]),
    (["node-0", "node-1", "node-3"], {"node-2": "x", "node-3": "y"},
     ["2 FailedNodes for 1 nodes not passed"]),
])
def test_a_filter_answer_is_held_to_the_reference_node_by_node(
        passed, failed, wrong):
    world, pod, asked = _world_with_one_anti_pod()
    found = extender_answers.compare(world, pod, asked, passed, failed)
    assert len(found) == len(wrong)
    assert all(w in f for w, f in zip(wrong, found))


def test_the_answers_check_rebuilds_the_cluster_at_each_kept_call():
    world, _pod, asked = _world_with_one_anti_pod()
    cfg_nodes = [{"metadata": {"name": n, "labels": world.labels[n]},
                  "status": {"allocatable": {"cpu": "4000m",
                                             "memory": "8388608Ki",
                                             "pods": "110"}}} for n in asked]
    shape = world.placed["there"][1]
    mk = lambda name, node="": {   # noqa: E731
        **shape, "metadata": {**shape["metadata"], "name": name},
        "spec": {**shape["spec"], "nodeName": node}}
    prebound = [mk("there", "node-2")]
    by_name = {n: mk(n) for n in ("job-1", "job-2", "warm")}
    history = [("bound", "warm-x", "node-0"), ("deleted", "warm-x", ""),
               ("bound", "job-1", "node-0"), ("bound", "job-2", "node-1")]
    extender_loop.KEPT.reset(every=1)
    try:
        # job-2 was filtered AFTER job-1's Binding: node-0 and node-2 held
        # pods of the group by then; an answer from a stale mirror passed
        # node-0
        extender_loop.KEPT.filters["job-2"] = (
            asked, ["node-0", "node-1", "node-3"], {"node-2": "anti"})
        extender_loop.KEPT.malformed.append("prioritize job-1: scored 11")
        looked, bad = extender_answers.replay(
            cfg_nodes, prebound, history, by_name, [shape], {})
        assert looked == 1 and len(bad) == 1
        assert "passed node-0, the reference refuses it" in bad[0]
        assert extender_answers.final_state(cfg_nodes, [], {}) == [
            "prioritize job-1: scored 11"]
    finally:
        extender_loop.KEPT.reset()
    assert extender_answers.final_state(cfg_nodes, [], {}) == []


def test_the_extender_roofline_counts_a_cycles_bytes_once_a_dispatch():
    from benchmarks.harness import roofline

    dims = {"N": 5120, "P": 8, "E": 65536, "R": 4, "L": 8, "K": 4, "SC": 64}
    obs = {"trace": {"busy_s": 2.0, "window_s": 20.0}, "rehearse": False,
           "dims": dims, "device": {"kind": "TPU v5 lite"},
           "waves": [{"dispatches": 3}, {"dispatches": 2}, {}]}
    got = extender_roofline.read(obs, {"kind": "extender_roofline"})
    assert got == pytest.approx(
        100 * 5 * roofline.cycle_bytes(dims) / 819e9 / 2.0)
    assert 0 < got < 100
    # a program that counts no dispatches (the parent), a CPU, no trace
    assert extender_roofline.read({**obs, "waves": [{}]}, {}) is None
    assert extender_roofline.read({**obs, "rehearse": True}, {}) is None
    assert extender_roofline.read({**obs, "trace": None}, {}) is None


# --------------------------------------------------------------------------- #
# ISSUE 37: the start's stages (`loop.children`) and the collector's pauses
# (`gc_*`: record fields that cover the interval since the record before)
# through their metric files
# --------------------------------------------------------------------------- #

from benchmarks.harness.sources import (  # noqa: E402
    interval_field,
    loop_children,
)

_PODS = "start/pods-sync"
_START = {  # a start's account, as the first record of a server carries it
    "start/nodes-sync": [1, 0.5, 0.5], _PODS: [1, 2.0, 2.0],
    _PODS + "/list": [1, 0.75, 0.75],
    _PODS + "/list/apiserver.list": [1, 0.7, 0.7],
    _PODS + "/list/apiserver.list/store.list": [1, 0.65, 0.65],
    _PODS + "/list/apiserver.list/store.list/kv": [1, 0.125, 0.125],
    _PODS + "/handlers": [1, 1.2, 1.2],
    _PODS + "/handlers/decode": [30000, 0.6, 0.001],
    "start/compile-ahead": [1, 1.5, 1.5]}


def _started(t_loop: float, first_gc: dict) -> dict:
    """A 40 s window that opened at t = 100: the first record carries the
    start (its `loop` began at `t_loop`), the second a later lap alone."""
    def rec(t, loop, gc_fields):
        loop["handlers"] = {"calls": 0, "wait_s": 0.0, "held_s": 0.0}
        return {"t_start": t, "duration_s": 1.0, "phases": [],
                "stats": {"attempted": 10}, "loop": loop, **gc_fields}
    return {"window_s": 40.0, "bound_in_window": 100, "series": {},
            "memory": {}, "trace": None, "rehearse": True, "waves": [
                rec(104.0, {"t_start": t_loop, "phases": [["start", 4.0]],
                            "children": _START}, first_gc),
                rec(120.0, {"t_start": 105.0, "phases": [["idle-wait", 15]]},
                    {"gc_full_collections": 1, "gc_pause_s": 0.25})]}


@pytest.mark.parametrize("path, seconds", [
    ("start/nodes-sync", 0.5), (_PODS + "/handlers/decode", 0.6),
    (_PODS + "/list/apiserver.list/store.list/kv", 0.125),
    ("start/recover", None)])
def test_the_start_reader_takes_a_path_of_the_first_records_loop(
        path, seconds):
    got = loop_children.read(_started(100.5, {}), {"path": path})
    assert got == (None if seconds is None else [seconds])


def test_the_start_reader_keeps_loops_window_rule_and_the_parents_silence():
    spec = {"path": "start/nodes-sync"}
    # a loop that began before the window opened (100) is not the window's
    assert loop_children.read(_started(70.0, {}), spec) is None
    # the parent: a `loop` without `children`, or no `loop` at all
    obs = _started(100.5, {})
    for w in obs["waves"]:
        w["loop"].pop("children", None)
    assert loop_children.read(obs, spec) is None
    assert loop_children.read({**obs, "waves": [
        {"t_start": 104.0, "stats": {"attempted": 1}}]}, spec) is None


@pytest.mark.parametrize("first_loop, pauses", [
    # the server started inside the window: its first record counts
    ({"t_start": 100.5, "phases": [["start", 3.5]]}, [0.75, 0.25]),
    # it started before the window can have opened (120 - 40): set-up's
    ({"t_start": 70.0, "phases": [["start", 34.0]]}, [0.25]),
    # no start: the stretch reaches back to set-up's last wave, 4.9 s before
    # the window opened, over the harness's own collections; `loop.py`'s
    # rule alone would keep it (REVIEW, PR 37)
    ({"t_start": 95.1, "phases": [["batch-wait", 8.9]]}, [0.25]),
    # a first record that does not say where its interval began
    (None, [0.25])])
def test_a_records_interval_is_the_windows_only_if_it_began_inside(
        first_loop, pauses):
    obs = _started(100.5, {"gc_full_collections": 2, "gc_pause_s": 0.75})
    obs["waves"][0]["loop"] = first_loop
    assert interval_field.read(obs, {"field": "gc_pause_s"}) == pauses
    # a later record without a `loop` (an extender's later pods) began where
    # the one before it ended: inside
    del obs["waves"][1]["loop"]
    assert interval_field.read(obs, {"field": "gc_pause_s"}) == pauses
    # no record has the field: nothing, not an empty sum
    assert interval_field.read(obs, {"field": "gc_minor"}) is None


@pytest.mark.parametrize("cell_name, expect, absent", [
    ("density-1k.backlog",
     {"start_nodes_sync_s": 0.5, "start_pods_sync_s": 2.0,
      "start_pods_list_s": 0.75, "start_pods_list_kv_s": 0.125,
      "start_pods_handlers_s": 1.2, "start_pods_decode_s": 0.6,
      "gc_pause_s.backlog": 1.0, "gc_full_collections": 3.0},
     ["start_compile_ahead_s", "gc_pause_s.arrivals"]),
    ("extender-5k.filter-prioritize",
     {"start_compile_ahead_s": 1.5, "start_pods_decode_s": 0.6,
      "gc_full_collections": 3.0}, ["gc_pause_s.arrivals"]),
    ("flagship-5k.arrivals", {"gc_pause_s.arrivals": 1.0},
     ["gc_pause_s.backlog", "gc_full_collections", "start_pods_sync_s"])])
def test_the_new_metrics_through_their_files(cell_name, expect, absent):
    obs = _started(100.5, {"gc_full_collections": 2, "gc_pause_s": 0.75})
    out = cell.compute_metrics(BENCH, "per_layer", cell_name, obs)
    assert {n: out[n]["value"] for n in expect} == pytest.approx(expect)
    assert not set(absent) & set(out)
    assert out[next(iter(expect))]["unit"] == "s"
    # a parent's records (no `children` on the loop, no `gc_*`) leave every
    # one of them out and keep the rest
    old = _started(100.5, {})
    for w in old["waves"]:
        w["loop"].pop("children", None)
        w.pop("gc_pause_s", None), w.pop("gc_full_collections", None)
    kept = cell.compute_metrics(BENCH, "per_layer", cell_name, old)
    new = {m["name"] for m in BENCH["per_layer"]
           if m["name"].startswith(("start_", "gc_"))}
    # PR 37's ten, PR 39's two that read the same `loop.children`
    # (`start_pods_list_wire_s`, `start_pods_list_decode_s`: the http cell's)
    # and PR 45's `start_volumes_sync_s` (the volume cell's)
    assert len(new) == 13 and set(kept) == set(out) - new
