"""Bin packing over an extended resource (ISSUE 53): upstream's documented
Policy, `RequestedToCapacityRatioPriority` with a shape and per-resource
weights, as a score of the engines' fused row, and the waves round's FILL
claim for a class whose score does not fall with its own placements.

Held here: the two probes that fail without either (a packing score through
waves lands where the scan lands; whole-node pods queued behind small ones
bind); the fused row against `api/semantics.py` and the benchmark's plain
reference (benchmarks/harness/checks/accelerators.py, which imports nothing of
the program) to the integer; waves against the scan on seeded accelerator
clusters; the default configuration unmoved; a Policy through
`SchedulerServer`; two Policies through one executable; the benchmark's
shape, check, readers, and the cell's rehearsal with its controls.
"""

import json
import random

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cell
from benchmarks.harness.checks import accelerators as ref
from benchmarks.harness.shapes import gpu_pool
from benchmarks.harness.sources import binpack_nodes, binpack_roofline
from kubernetes_tpu.api import semantics
from kubernetes_tpu.api.types import (NUM_FIXED_RES, Node, Pod, Resources,
                                      Taint, TaintEffect, Toleration,
                                      TolerationOp)
from kubernetes_tpu.ops.fit import _pct_floor
from kubernetes_tpu.ops.lattice import default_engine_config
from kubernetes_tpu.sched.config import load_config
from kubernetes_tpu.sched.cycle import (UNSCHEDULABLE_TAINT_KEY,
                                        _schedule_batch,
                                        _schedule_batch_impl, _scores)
from kubernetes_tpu.state.dims import Dims
from kubernetes_tpu.state.encode import Encoder

from test_daemon_pins import rehearse, wait_for  # noqa: E402

ROOT = cell.ROOT
BENCH = cell.load_json(ROOT, "BENCHMARK.json")
CFG = cell.load_json(ROOT, "benchmarks", "configs", "gpu-binpack-5k.json")
SMALL = {**CFG, **CFG["rehearse"]}
CELL = "gpu-binpack-5k.backlog"
GPU = "nvidia.com/gpu"
TOLERATE_GPU = (Toleration(key=GPU, op=TolerationOp.EXISTS,
                           effect=TaintEffect.NO_SCHEDULE),)
GPU_TAINT = (Taint(GPU, "present", TaintEffect.NO_SCHEDULE),)
#: one Dims bucket for every engine case below: ONE compiled program each
BASE = Dims(N=32, P=128, E=64, R=8, SC=16)


def policy(shape=((0, 0), (100, 10)),
           resources=((GPU, 5), ("cpu", 1), ("memory", 1)), weight=2) -> dict:
    """The cell's Policy as a KubeSchedulerConfiguration."""
    return {"kind": "KubeSchedulerConfiguration", "algorithmSource": {
        "policy": {"inline": {"kind": "Policy", "apiVersion": "v1",
                              "priorities": [
            {"name": "RequestedToCapacityRatioPriority", "weight": weight,
             "argument": {"requestedToCapacityRatioArguments": {
                 "shape": [{"utilization": u, "score": s} for u, s in shape],
                 "resources": [{"name": n, "weight": w}
                               for n, w in resources]}}},
            {"name": "TaintTolerationPriority", "weight": 1},
            {"name": "NodeAffinityPriority", "weight": 1},
            {"name": "InterPodAffinityPriority", "weight": 1}]}}}}


def encode(nodes, existing, pending, base=BASE):
    enc = Encoder()
    enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
    enc.vocabs.label_vals.intern("")
    slot = lambda name: NUM_FIXED_RES + enc.vocabs.resources.intern(name)
    slot(GPU)     # as SchedulerServer interns the weight map's names
    tables, ex, pe, d = enc.encode_cluster(nodes, existing, pending, base)
    keys = (jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY)),
            jnp.int32(enc.vocabs.label_vals.get("")))
    return tables, ex, pe, d, keys, slot


def run(nodes, existing, pending, engine, config=None, ecfg=None):
    tables, ex, pe, d, keys, slot = encode(nodes, existing, pending)
    assert (d.N, d.P, d.R, d.SC) == (BASE.N, BASE.P, BASE.R, BASE.SC)
    if config is not None:
        ecfg = load_config(config).engine_config(slot)
    res = _schedule_batch(tables, pe, keys, d.D, ex, ecfg=ecfg,
                          engine=engine)
    return np.asarray(res.node)[:len(pending)], res


def cpu_node(i, cpu="8", memory="32Gi"):
    return Node(name=f"n{i}", labels={"kubernetes.io/hostname": f"n{i}"},
                allocatable=Resources.make(cpu=cpu, memory=memory, pods=110))


def gpu_node(i, gpus=8, tainted=True):
    return Node(name=f"g{i}", labels={"kubernetes.io/hostname": f"g{i}"},
                taints=GPU_TAINT if tainted else (),
                allocatable=Resources.make(cpu="96", memory="1024Gi",
                                           pods=110, scalars={GPU: gpus}))


def gpu_pod(name, k, at, node=""):
    return Pod(name=name, creation_index=at, node_name=node,
               tolerations=TOLERATE_GPU,
               requests=Resources.make(cpu=str(10 * k), memory=f"{100 * k}Gi",
                                       scalars={GPU: k}))


def plain_pod(name, at, cpu="500m", memory="1Gi", node=""):
    return Pod(name=name, creation_index=at, node_name=node,
               requests=Resources.make(cpu=cpu, memory=memory))


# --------------------------------------------------------------------- #
# (a) the two probes: fail on the parent, pass here
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("engine", ["waves", "scan"])
def test_a_packing_score_fills_the_node_the_scan_fills(engine):
    """8 one-CPU pods, 8 empty 8-CPU nodes, MostAllocated at weight 1 and the
    two spreading resource scores off: the sequential loop puts all 8 on n0
    (each placement makes n0 MORE attractive). The parent's waves round
    claimed one pod a node and used all 8 nodes."""
    nodes = [cpu_node(i) for i in range(8)]
    pods = [plain_pod(f"p{i}", i, cpu="1") for i in range(8)]
    ecfg = default_engine_config()._replace(
        w_least=0.0, w_balanced=0.0, w_most=1.0)
    node, res = run(nodes, [], pods, engine, ecfg=ecfg)
    assert node.tolist() == [0] * 8
    if engine == "waves":
        assert np.asarray(res.fill).tolist() == [1, 8, 1]
        assert int(res.rounds) == 1


@pytest.mark.parametrize("engine", ["waves", "scan"])
def test_whole_node_pods_queued_behind_small_ones_all_bind(engine):
    """16 one-GPU pods ahead of 5 eight-GPU pods on 8 eight-GPU nodes. Under
    the cell's Policy the small pods take 2 nodes and all 5 whole-node pods
    bind; under the default provider (and under the Policy on the parent,
    whose round claimed a node a pod) they touch all 8 and none binds."""
    nodes = [gpu_node(i) for i in range(8)]
    pods = [gpu_pod(f"s{i}", 1, i) for i in range(16)] \
        + [gpu_pod(f"w{i}", 8, 16 + i) for i in range(5)]
    node, _res = run(nodes, [], pods, engine, config=policy())
    assert (node >= 0).all()
    assert len(set(node[:16].tolist())) == 2
    assert len(set(node[16:].tolist())) == 5
    spread, _res = run(nodes, [], pods, engine)
    assert len(set(spread[:16].tolist())) == 8 and (spread[16:] < 0).all()


# --------------------------------------------------------------------- #
# (b) the fused row, to the integer
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("num,den", [
    (0, 1), (1, 1), (3, 8), (7, 8), (1, 3), (2 ** 30 - 1, 2 ** 30),
    (134217728 - 1048576, 134217728), (2 ** 31 - 2, 2 ** 31 - 1),
    (123456789, 987654321)])
def test_the_percentage_is_the_integer_quotient_where_int32_overflows(
        num, den):
    got = _pct_floor(jnp.asarray([num], jnp.int32),
                     jnp.asarray([den], jnp.int32))
    assert int(got[0]) == num * 100 // den


def test_the_documentations_worked_example_reads_5():
    """Resource Bin Packing for Extended Resources: intel.com/foo 2 asked of
    4 with 1 used (75 -> 7), memory 256MB of 1GB with 256MB used (50 -> 5),
    cpu 2 of 8 with 1 used (100 - 62 -> 3); weights 5 / 1 / 3; shape 0:0,
    100:10. (7 * 5 + 5 * 1 + 3 * 3) / 9 = 5 on the 0..10 scale the page
    computes in (v1.16); with the shape's scores scaled to 0..100 as v1.17
    scales them, (75 * 5 + 50 + 38 * 3) / 9 = 59.9 -> 60."""
    foo = "intel.com/foo"
    req = Resources.make(cpu="2", memory="256Mi", scalars={foo: 2})
    used = Resources.make(cpu="1", memory="256Mi", scalars={foo: 1})
    alloc = Resources.make(cpu="8", memory="1Gi", scalars={foo: 4})
    res = ((foo, 5), ("memory", 1), ("cpu", 3))
    total = {foo: 3, "memory": 512 * 1024, "cpu": 3000}
    have = {foo: 4, "memory": 1024 * 1024, "cpu": 8000}
    for shape, want in ((((0, 0), (100, 10)), 5), (((0, 0), (100, 100)), 60)):
        assert semantics.requested_to_capacity_ratio_score(
            req, used, alloc, shape, res) == want
        assert ref.rtc_score(total, have, list(shape), list(res)) == want


SHAPES = {
    "documented": ((0, 0), (100, 10)),
    "three-point": ((0, 0), (40, 8), (100, 10)),
    "falls-then-rises": ((10, 9), (50, 2), (90, 7)),
    "least-utilized": ((0, 10), (100, 0)),
}


def seeded_state(seed):
    """Nodes with and without the accelerator, some of it used, one over its
    memory; pods that ask it, that ask none of it, that ask no cpu."""
    rng = random.Random(seed)
    nodes, existing = [], []
    for i in range(12):
        if rng.random() < 0.5:
            n = gpu_node(i, gpus=rng.choice([4, 8]), tainted=False)
            for j in range(rng.randint(0, 3)):
                existing.append(gpu_pod(f"e{i}-{j}", rng.choice([1, 2]),
                                        0, node=n.name))
        else:
            n = cpu_node(i, cpu=rng.choice(["8", "32"]),
                         memory=rng.choice(["32Gi", "128Gi"]))
            for j in range(rng.randint(0, 4)):
                existing.append(plain_pod(
                    f"e{i}-{j}", 0, cpu=rng.choice(["250m", "1", "3"]),
                    memory=rng.choice(["1Gi", "6Gi"]), node=n.name))
        nodes.append(n)
    pending = [gpu_pod("a", 1, 1), gpu_pod("b", 4, 2),
               plain_pod("c", 3), plain_pod("d", 4, cpu="2", memory="5Gi"),
               Pod(name="e", creation_index=5,
                   requests=Resources.make(memory="1Gi")),
               Pod(name="f", creation_index=6, tolerations=TOLERATE_GPU,
                   requests=Resources.make(cpu="1", scalars={GPU: 2}))]
    return nodes, existing, pending


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("seed", range(3))
def test_the_fused_score_equals_the_oracle_and_the_plain_reference(
        seed, shape):
    nodes, existing, pending = seeded_state(seed)
    resources = ((GPU, 5), ("cpu", 1), ("memory", 1))
    tables, ex, pe, d, keys, slot = encode(nodes, existing, pending)
    cfg = load_config(policy(SHAPES[shape], resources, weight=1))
    # the priority alone, so that the row IS the score
    ecfg = cfg.engine_config(slot)._replace(w_taint=0.0, w_node_affinity=0.0,
                                            w_interpod=0.0)
    got = np.asarray(_scores(tables, pe, keys, d.D, ex, 1.0, ecfg))
    points = [(u, s * 10) for u, s in SHAPES[shape]]
    seen = 0
    for i, pod in enumerate(pending):
        for j, node in enumerate(nodes):
            if got[i, j] == -np.inf:
                continue
            mine = [p for p in existing if p.node_name == node.name]
            used = Resources(
                milli_cpu=sum(p.requests.milli_cpu for p in mine),
                memory_kib=sum(p.requests.memory_kib for p in mine),
                scalars=((GPU, sum(dict(p.requests.scalars).get(GPU, 0)
                                   for p in mine)),))
            want = semantics.requested_to_capacity_ratio_score(
                pod.requests, used, node.allocatable, points, resources)
            assert got[i, j] == want, (pod.name, node.name)
            # and the benchmark's reference, from plain numbers
            asked = {"cpu": pod.requests.milli_cpu or 100,
                     "memory": pod.requests.memory_kib or 200 * 1024,
                     GPU: dict(pod.requests.scalars).get(GPU, 0)}
            total = {"cpu": used.milli_cpu + asked["cpu"],
                     "memory": used.memory_kib + asked["memory"],
                     GPU: dict(used.scalars)[GPU] + asked[GPU]}
            have = {"cpu": node.allocatable.milli_cpu,
                    "memory": node.allocatable.memory_kib,
                    GPU: dict(node.allocatable.scalars).get(GPU, 0)}
            assert ref.rtc_score(total, have, points,
                                 list(resources)) == want
            seen += 1
    assert seen > 30


# --------------------------------------------------------------------- #
# (c) waves against the scan on seeded accelerator clusters
# --------------------------------------------------------------------- #

#: accelerator nodes waves may open beyond the scan's on the clusters below:
#: a class's round fills from ITS best node on, so the last node of each of
#: the four sizes may be left part full where the scan's one queue shares it
OPENED_SLACK = 3


def accelerator_cluster(seed):
    rng = random.Random(seed)
    nodes = [gpu_node(i) for i in range(12)] + [cpu_node(i, cpu="32",
                                                         memory="128Gi")
                                                for i in range(8)]
    existing = [gpu_pod(f"run-{i}", 8, 0, node=f"g{i}") for i in (0, 5)]
    existing += [plain_pod(f"base-{i}", 0, node=f"n{i}") for i in range(8)]
    pending = [gpu_pod(f"one-{i}", 1, 0) for i in range(18)] \
        + [gpu_pod(f"two-{i}", 2, 0) for i in range(6)] \
        + [gpu_pod(f"four-{i}", 4, 0) for i in range(3)] \
        + [gpu_pod(f"whole-{i}", 8, 0) for i in range(4)] \
        + [plain_pod(f"plain-{i}", 0) for i in range(40)]
    rng.shuffle(pending)
    for at, p in enumerate(pending):
        p.creation_index = at + 1
    return nodes, existing, pending   # 74 of 80 free GPUs asked


@pytest.mark.parametrize("seed", range(4))
def test_waves_pack_as_the_scan_does_on_seeded_accelerator_clusters(seed):
    nodes, existing, pending = accelerator_cluster(seed)
    opened = {}
    for engine in ("waves", "scan"):
        node, res = run(nodes, existing, pending, engine, config=policy())
        assert (node >= 0).all(), engine       # the whole-node pods too
        used = {n.name: [0, 0, 0, 0] for n in nodes}
        for p, at in list(zip(pending, node)) + [
                (p, next(i for i, n in enumerate(nodes)
                         if n.name == p.node_name)) for p in existing]:
            n = nodes[at]
            assert semantics.pod_tolerates_node_taints(p, n), p.name
            u = used[n.name]
            u[0] += p.requests.milli_cpu
            u[1] += p.requests.memory_kib
            u[2] += dict(p.requests.scalars).get(GPU, 0)
            u[3] += 1
        for n in nodes:
            a = n.allocatable
            assert all(x <= cap for x, cap in zip(used[n.name], (
                a.milli_cpu, a.memory_kib, dict(a.scalars).get(GPU, 0),
                a.pods))), n.name
        opened[engine] = len({nodes[at].name for p, at in zip(pending, node)
                              if p.requests.scalars})
        if engine == "waves":
            classes, pods, rounds = np.asarray(res.fill).tolist()
            assert (classes, pods) == (5, len(pending)) and rounds <= 6
    assert opened["scan"] == 10          # 74 GPUs on the 10 empty nodes
    assert opened["waves"] <= opened["scan"] + OPENED_SLACK


# --------------------------------------------------------------------- #
# (d) the default configuration is unmoved
# --------------------------------------------------------------------- #

def test_no_class_fills_under_the_default_provider():
    """Flagship-shaped classes under the default scores: nothing fills, so
    the round claims a node a pod as before (tests/test_waves.py holds the
    placements to the arrays recorded from PR 37's parent, and counts the
    round's gathers and scatters)."""
    from kubernetes_tpu.models.workloads import flagship_pods, make_nodes

    nodes = make_nodes(24, zones=4, racks_per_zone=2)
    tables, ex, pe, d, keys, _slot = encode(
        nodes, [], flagship_pods(96, groups=6), Dims(N=32, P=128, E=64))
    res = _schedule_batch(tables, pe, keys, d.D, ex, engine="waves")
    assert np.asarray(res.fill).tolist() == [0, 0, 0]
    assert (np.asarray(res.node)[:96] >= 0).sum() > 50
    # a packing score alone does not make a class with a quota fill
    ecfg = default_engine_config()._replace(w_least=0.0, w_balanced=0.0,
                                            w_most=1.0)
    res = _schedule_batch(tables, pe, keys, d.D, ex, ecfg=ecfg,
                          engine="waves")
    assert int(np.asarray(res.fill)[0]) == 0


# --------------------------------------------------------------------- #
# (e) the Policy surface
# --------------------------------------------------------------------- #

def test_two_policies_that_differ_in_shape_and_weights_run_one_executable():
    nodes = [gpu_node(i) for i in range(8)]
    pods = [gpu_pod(f"s{i}", 1, i) for i in range(16)]
    run(nodes, [], pods, "waves", config=policy())
    before = _schedule_batch_impl._cache_size()
    packed, _ = run(nodes, [], pods, "waves", config=policy(
        ((0, 0), (50, 3), (100, 10)), ((GPU, 3), ("cpu", 2))))
    spread, _ = run(nodes, [], pods, "waves", config=policy(
        SHAPES["least-utilized"], ((GPU, 1),), weight=7))
    assert _schedule_batch_impl._cache_size() == before
    assert len(set(packed.tolist())) == 2 and len(set(spread.tolist())) == 8


def test_the_policys_argument_reaches_the_engine_config():
    cfg = load_config(policy(((0, 0), (40, 8), (100, 10)),
                             ((GPU, 5), ("cpu", 1), ("memory", 2))))
    assert "RequestedToCapacityRatio" in cfg.plugins.score.enabled
    assert "NodeResourcesLeastAllocated" not in cfg.plugins.score.enabled
    e = cfg.engine_config(lambda name: {GPU: 6}[name])
    assert (float(e.w_rtc), float(e.w_least), float(e.w_balanced)) \
        == (2.0, 0.0, 0.0)
    assert e.rtc_x.tolist() == [0, 40] + [100] * 6
    assert e.rtc_y.tolist() == [0, 80] + [100] * 6
    assert e.rtc_w.tolist() == [1, 2, 0, 0, 0, 0, 5] + [0] * 9
    # no argument: the default weight map, cpu 1 and memory 1
    bare = load_config({"policy": {"kind": "Policy", "priorities": [
        {"name": "RequestedToCapacityRatioPriority", "weight": 1}]}})
    assert bare.engine_config().rtc_w.tolist() == [1, 1] + [0] * 14
    # the default provider carries the score at weight 0
    assert float(default_engine_config().w_rtc) == 0.0


@pytest.mark.parametrize("argument,complaint", [
    ({"serviceAntiAffinity": {"label": "zone"}}, "not supported"),
    ({"requestedToCapacityRatioArguments": {"shape": [
        {"utilization": 50, "score": 1}, {"utilization": 50, "score": 2}]}},
     "strictly increasing"),
    ({"requestedToCapacityRatioArguments": {"shape": [
        {"utilization": 0, "score": 11}]}}, "score 0..10"),
    ({"requestedToCapacityRatioArguments": {"shape": [
        {"utilization": 10 * i, "score": 1} for i in range(9)]}},
     "at most 8"),
    ({"requestedToCapacityRatioArguments": {
        "shape": [{"utilization": 0, "score": 0}],
        "resources": [{"name": "cpu", "weight": 0}]}}, "weight"),
    ({"requestedToCapacityRatioArguments": {
        "shape": [{"utilization": 0, "score": 0}],
        "resources": [{"name": "pods", "weight": 1}]}}, "extended resource"),
])
def test_an_argument_the_scheduler_cannot_honour_is_an_error(argument,
                                                             complaint):
    with pytest.raises(ValueError, match=complaint):
        load_config({"policy": {"kind": "Policy", "priorities": [
            {"name": "Custom", "weight": 1, "argument": argument}]}})
    with pytest.raises(ValueError, match="not supported"):
        load_config({"policy": {"kind": "Policy", "predicates": [
            {"name": "Zoned", "argument": {"serviceAffinity": {
                "labels": ["zone"]}}}]}})


def test_a_policy_file_through_the_server_packs_and_says_so(tmp_path):
    """The Policy in a FILE, named by a KubeSchedulerConfiguration handed to
    SchedulerServer: accelerator pods bound packed, a plain pod never on the
    tainted pool, the wave's record and the counters."""
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.sched import metrics
    from kubernetes_tpu.sched.server import SchedulerServer

    cfg = {**SMALL, "nodes": 10, "zones": 2, "racks_per_zone": 2,
           "pool": {**SMALL["pool"], "nodes": 4},
           "plain": {**SMALL["plain"], "pods": 12},
           "sizes": [{"gpus": 1, "pods": 8}, {"gpus": 8, "pods": 2}],
           "backlog_pods": 22, "existing_pods": 19}
    path = tmp_path / "policy.json"
    path.write_text(json.dumps(cfg["policy"]))
    pop = gpu_pool.Population(cfg, 7, cfg["backlog_pods"])
    api = APIServer()
    client = Client.local(api)
    for n in gpu_pool.make_nodes(cfg):
        client.nodes.create(n)
    for p in pop.prebound(cfg["nodes"], cfg["existing_pods"]):
        client.pods.create(p)
    pods = pop.pending(cfg["backlog_pods"], 7, "job")
    for p in pods:
        client.pods.create(p)
    fill0 = metrics.FILL_PODS.value()
    ext0 = metrics.EXTENDED_RESOURCE_PODS.value(resource=GPU,
                                                result="scheduled")
    server = SchedulerServer(
        client, config={"kind": "KubeSchedulerConfiguration",
                        "algorithmSource": {"policy": {"file": {
                            "path": str(path)}}}},
        base_dims=Dims(N=16, P=64, E=64, R=8), batch_size=64,
        cycle_interval=0.02, batch_window=0.05)
    server.start()
    try:
        def bound():
            return {p["metadata"]["name"]: p["spec"].get("nodeName")
                    for p in client.pods.list("default")["items"]
                    if p["metadata"]["name"].startswith("job-")}

        assert wait_for(lambda: all(bound().values())), bound()
        at = bound()
        nodes = client.nodes.list()["items"]
        listing = client.pods.list("default")["items"]
        assert {k: len(v) for k, v in ref.counts(nodes, listing, {}).items()
                } == dict.fromkeys(ref.COUNTS, 0)
        pool = {f"node-{i}" for i in pop.pool}
        small = {at[p["metadata"]["name"]] for p in pods
                 if pop.group_of(p) == 1}
        whole = {at[p["metadata"]["name"]] for p in pods
                 if pop.group_of(p) == 2}
        plain = {at[p["metadata"]["name"]] for p in pods
                 if pop.group_of(p) == 0}
        # 3 empty accelerator nodes: the 8 one-GPU pods on ONE of them, the
        # two whole-node pods on the other two
        assert small <= pool and len(small) == 1 and len(whole) == 2
        assert not plain & pool
        recs = [r for r in server.scheduler.telemetry.recorder.records()
                if r.get("fill_classes")]
        assert recs and sum(r["fill_pods"] for r in recs) == 22
        assert sum(r["extended_pods"] for r in recs) == 10
        assert all(r["rtc_resources"] == 3 and r["fill_rounds"] >= 1
                   for r in recs)
        assert wait_for(lambda: metrics.FILL_PODS.value() - fill0 == 22)
        assert metrics.EXTENDED_RESOURCE_PODS.value(
            resource=GPU, result="scheduled") - ext0 == 10
    finally:
        server.stop()
        api.close()


# --------------------------------------------------------------------- #
# (f) the benchmark's side: shape, reference, readers, the cell
# --------------------------------------------------------------------- #

def test_a_trace_of_the_four_chip_host_reads_the_one_chip_that_ran():
    """The cell holds four chips and the program runs on the first: the
    profiler's planes of the three idle ones hold no operation, and the
    reducer's busy seconds are the one chip's, not a quarter of them."""
    from benchmarks.harness import trace

    ms = 1_000_000
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [trace.MARK_OPEN, 0, 10], [trace.MARK_CLOSE, 1000 * ms, 10]]}]}
    ran = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_x", 100 * ms, 300 * ms]]},
        {"name": "XLA Ops", "events": [["fusion.1", 100 * ms, 50 * ms],
                                       ["sort.2", 300 * ms, 100 * ms]]}]}
    idle = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops", "events": []}, {"name": "Steps", "events": []}]}
        for i in (1, 2, 3)]
    waves = [{"t_start": 50.1, "phases": [("dispatch", 0.3)]}]
    red = trace.reduce_trace({"planes": [host, ran, *idle]}, 50.0, 51.0,
                             waves)
    assert red["chips"] == 1
    assert red["busy_s"] == pytest.approx(0.15)
    assert red["window_s"] == pytest.approx(1.0)
    assert [n for n, _ in red["device_ops"]] == ["sort.2", "fusion.1"]
    assert sum(t for _, t in red["idle_gaps"]) == pytest.approx(0.85)


def test_the_cell_names_its_modules_and_its_capacities():
    from benchmarks.harness.wirings import local_policy

    c, cfg, tr = cell.find_cell(BENCH, CELL)
    # four chips are the whole host, held for steadiness alone (the driver's
    # first check refused the cell's spread on one): the program uses one
    assert (c["config"], c["traffic"], c["chips"], tr["kind"]) == (
        "gpu-binpack-5k", "gpu-restart-backlog", 4, "pool_backlog")
    assert cfg["chips"] == 1 and "steadiness alone" in c["why"]
    plugs = cell.plug_ins(BENCH, "per_layer", CELL, cfg, tr)
    assert plugs["shapes"].__name__.endswith("shapes.gpu_pool")
    assert plugs["kind"].__name__.endswith("kinds.pool_backlog")
    assert plugs["wiring"] is local_policy
    assert [n for n, _ in plugs["checks"]] == ["placement", "accelerators"]
    conf = next(x for x in BENCH["configs"] if x["name"] == "gpu-binpack-5k")
    assert conf["reduced"] == [] and cfg["reduced"] == {}
    assert len(conf["source"]) <= 200 and conf["source"] == cfg["source"]
    d = local_policy.Cluster(cfg).dims
    assert (d.N, d.P, d.E, d.R, d.SC) == (5120, 10240, 32768, 8, 64)
    assert CELL in next(m for m in BENCH["end_to_end"]
                        if m["name"] == "drain_pods_per_s")["workloads"]
    # the Policy the cell runs under loads, and fills
    e = load_config(local_policy.Cluster(cfg).scheduler_config()
                    ).engine_config(lambda name: 4)
    assert float(e.w_rtc) == 2.0 and e.rtc_w.tolist()[:5] == [1, 1, 0, 0, 5]


def test_the_shape_is_the_same_work_whatever_the_seed():
    seen = []
    for seed in (1, 2 ** 31 + 5):
        pop = gpu_pool.Population(SMALL, seed, SMALL["backlog_pods"])
        pend = pop.pending(SMALL["backlog_pods"], seed, "job")
        warm = pop.pending(pop.n, seed, "warm0")
        assert (len(pend), len(warm)) == (124, 5)
        names = [p["metadata"]["name"] for p in pend + warm]
        assert len(set(names)) == len(names)
        seen.append((sorted(json.dumps(p["spec"], sort_keys=True)
                            for p in pend), names))
    assert seen[0][0] == seen[1][0] and seen[0][1] != seen[1][1]
    with pytest.raises(SystemExit):
        gpu_pool.Population({**SMALL, "existing_pods": 5}, 1, 124)
    # the published size states its own counts
    big = gpu_pool.Population(CFG, 1, CFG["backlog_pods"])
    nodes = gpu_pool.make_nodes(CFG)
    pool = [n for n in nodes if GPU in n["status"]["allocatable"]]
    assert (len(pool), len(big.whole), len(big.cpu_nodes)) == (2000, 500,
                                                               3000)
    assert all(n["spec"]["taints"] == [CFG["pool"]["taint"]] for n in pool)
    pend = big.pending(CFG["backlog_pods"], 3, "job")
    asked = [sum(ref.asks(p).values()) for p in pend]
    assert sorted(set(asked)) == [0, 1, 2, 4, 8]
    assert [asked.count(k) for k in (0, 1, 2, 4, 8)] == [6000, 1890, 756,
                                                         567, 567]
    assert sum(asked) == 10206    # of the 1,500 empty nodes' 12,000
    bound = big.prebound(5000, 9500)
    assert len(bound) == 9500
    assert not ref.counts(nodes, bound, {})["nodes_over_extended_resource"]
    assert not ref.counts(nodes, bound, {})["pods_on_untolerated_taint"]


def small_listing():
    pop = gpu_pool.Population(SMALL, 3, SMALL["backlog_pods"])
    nodes = gpu_pool.make_nodes(SMALL)
    return pop, nodes, pop.prebound(64, SMALL["existing_pods"])


def test_the_reference_sees_each_violation():
    pop, nodes, bound = small_listing()
    free = next(f"node-{i}" for i in pop.pool if i not in pop.whole)
    cpu = f"node-{pop.cpu_nodes[0]}"
    clean = ref.counts(nodes, bound, {})
    assert {k: len(v) for k, v in clean.items()} == dict.fromkeys(
        ref.COUNTS, 0)
    # nine accelerators asked of a node's eight
    nine = bound + [pop.pod(1, f"x{i}", free) for i in range(9)]
    assert len(ref.counts(nodes, nine, {})[
        "nodes_over_extended_resource"]) == 1
    # an accelerator pod on a node that has none
    lacks = bound + [pop.pod(2, "y", cpu)]
    assert len(ref.counts(nodes, lacks, {})[
        "nodes_over_extended_resource"]) == 1
    # a plain pod on the tainted pool
    stray = bound + [pop.pod(0, "z", free)]
    assert len(ref.counts(nodes, stray, {})[
        "pods_on_untolerated_taint"]) == 1
    # the replay: the ninth Binding is the one refused, in the world as it
    # stood; a deletion frees what it held
    pods = {f"x{i}": pop.pod(1, f"x{i}") for i in range(10)}
    history = [("bound", f"x{i}", free) for i in range(9)]
    looked, refused = ref.replay(nodes, bound, history, pods, [], {})
    assert looked == 9 and len(refused) == 1 and "x8" in refused[0]
    history = history[:8] + [("deleted", "x0", ""), ("bound", "x8", free)]
    assert ref.replay(nodes, bound, history, pods, [], {})[1] == []
    plain = {"p": pop.pod(0, "p")}
    assert ref.replay(nodes, bound, [("bound", "p", free)], plain, [],
                      {}) == (0, [])   # `placement` and the taints' count


def test_the_sequential_reference_packs_and_a_spreader_would_not():
    pop, nodes, bound = small_listing()
    queue = sorted(pop.pending(SMALL["backlog_pods"], 3, "job"),
                   key=lambda p: p["metadata"]["name"])
    placed = ref.sequential(nodes, bound, queue, SMALL["policy"])
    assert all(placed.values())
    # 128 GPUs asked: 16 nodes full, none beside them opened
    assert ref.opened(nodes, placed, GPU) == 16
    listing = bound + [{**p, "spec": {**p["spec"], "nodeName": placed[
        p["metadata"]["name"]]}} for p in queue]
    assert {k: len(v) for k, v in ref.counts(nodes, listing, {}).items()
            } == dict.fromkeys(ref.COUNTS, 0)
    from benchmarks.harness import reference

    assert reference.final_state(nodes, listing, check_spread=True) == []


NEW_METRICS = ["fill_pods_first", "fill_rounds_first",
               "gpu_nodes_opened_over_reference", "rtc_score_resources",
               "binpack_engine_roofline_pct"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_has_its_file_and_lists_the_cell(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "drain_pods_per_s"
    spec = cell.load_json(ROOT, "benchmarks", "metrics", name + ".json")
    assert spec["name"] == name and spec["layer"] == entry["layer"]
    cell.plug_in("sources", spec["source"]["kind"])


def test_the_new_metrics_read_the_record_and_a_parents_gives_nothing():
    rec = {"fill_classes": 5, "fill_pods": 9780, "fill_rounds": 6,
           "extended_pods": 3780, "rtc_resources": 3, "phases": [],
           "device_split": {"execute_s": 0.01}}
    obs = {"waves": [rec], "series": {}, "memory": {},
           "bound_in_window": 9780, "window_s": 9.0, "rehearse": True,
           "device": {"kind": "cpu"}, "dims": {}, "trace": None}
    bench = {"per_layer": [m for m in BENCH["per_layer"]
                           if m["name"] in NEW_METRICS]}
    binpack_nodes.NOTED.clear()
    got = cell.compute_metrics(bench, "per_layer", CELL, obs)
    assert {k: v["value"] for k, v in got.items()} == {
        "fill_pods_first": 9780.0, "fill_rounds_first": 6.0,
        "rtc_score_resources": 3.0}
    binpack_nodes.note(1290, 1280)
    got = cell.compute_metrics(bench, "per_layer", CELL, obs)
    assert got["gpu_nodes_opened_over_reference"]["value"] == 1290 / 1280
    binpack_nodes.NOTED.clear()
    obs["waves"] = [{"phases": [], "device_split": {"execute_s": 0.01}}]
    assert cell.compute_metrics(bench, "per_layer", CELL, obs) == {}


def test_the_binpack_roofline_reader_counts_the_fills_bytes():
    from benchmarks.harness import roofline

    dims = {"N": 5120, "P": 10240, "E": 32768, "R": 8, "L": 8, "K": 4,
            "SC": 64}
    assert binpack_roofline.fill_bytes(dims) == 4 * 64 * 8 + 12 * 64 * 5120
    obs = {"waves": [{"device_split": {"execute_s": 0.01},
                      "fill_classes": 5}, {"fill_classes": 5},
                     {"device_split": {"execute_s": 0.01}}],
           "trace": {"busy_s": 0.05}, "rehearse": False, "dims": dims,
           "device": {"kind": "TPU v5 lite"}}
    least = (roofline.cycle_bytes(dims) + binpack_roofline.fill_bytes(dims)
             ) / 819e9
    got = binpack_roofline.read(obs, {})
    assert got == pytest.approx(100.0 * least / 0.05) and got < 105
    # R is in the count: the cell's extended resource widens every node row
    assert roofline.cycle_bytes(dims) - roofline.cycle_bytes(
        {**dims, "R": 4}) == 5120 * 2 * 4 * 4
    obs["waves"] = [{"device_split": {"execute_s": 0.01}}]
    assert binpack_roofline.read(obs, {}) is None
    assert binpack_roofline.read({**obs, "trace": None}, {}) is None


def test_the_cell_rehearses_correct_and_opens_what_the_reference_opens():
    p, lines = rehearse(["benchmarks/run.py", "--workload", CELL, "--seed",
                         str(2 ** 31 + 11), "--seconds", "40", "--trace",
                         "1", "--rehearse"])
    res = lines[-1]
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["attempted"] == 124
    for name in ("nodes_over_extended_resource", "pods_never_bound",
                 "extended_bindings_refused_at_their_turn",
                 "pods_on_untolerated_taint", "compilations_in_window"):
        assert res["checks"][name]["value"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["fill_pods_first"] == 124 and got["rtc_score_resources"] == 3
    assert 1 <= got["fill_rounds_first"] <= 6
    assert got["gpu_nodes_opened_over_reference"] <= 19 / 16
    info = json.loads(next(ln for ln in p.stdout.splitlines()
                           if ln.startswith("info "))[5:])
    assert info["n_waves"] == 1 and info["dims"]["R"] == 8
    assert info["gpu_nodes_opened_reference"] == 16
    assert info["reference_left_pending"] == 0


@pytest.mark.parametrize("control,failed,others", [
    ("default_provider_scores", "pods_never_bound", ()),
    ("ignore_extended_resources", "nodes_over_extended_resource",
     ("extended_bindings_refused_at_their_turn",)),
])
def test_a_scheduler_without_the_policy_or_the_resource_is_not_correct(
        control, failed, others):
    _p, lines = rehearse([
        "benchmarks/tests/chip_control_binpack.py", "--workload", CELL,
        "--control", control, "--seeds", "3", "--seconds", "40",
        "--rehearse"])
    run_, summary = lines[-2], lines[-1]
    assert summary["not_correct"] == 1 and not run_["correct"]
    wrong = {k for k, v in run_["checks"].items() if v["value"]}
    assert wrong == {failed, *others}, run_["checks"]
    if control == "default_provider_scores":
        # the 7 whole-node pods, and those alone
        assert run_["checks"]["pods_never_bound"]["value"] == 7
