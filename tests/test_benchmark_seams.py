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
