"""Wave-parallel assignment (ops/waves.py) correctness.

Two rungs, mirroring how the reference validates its scheduling algorithm
(table-driven unit tests + randomized integration):

1. EXACT equivalence with the sequential-assume scan on workloads where both
   must produce the same placements (homogeneous resource pods: wave-start
   scores stay distinct-node-optimal within a wave);
2. the SOUNDNESS invariant on randomized adversarial clusters: the wave
   output replayed in (wave, queue-order) must pass the full pure-Python
   predicate oracle at every step — i.e. the result is a valid greedy
   execution of the reference's one-pod-at-a-time loop
   (scheduler.go:596-763), just a different interleaving than the scan's.
"""

import dataclasses
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api.types import Node, Pod, Resources
from kubernetes_tpu.ops.assign import assign_batch, initial_state
from kubernetes_tpu.ops.lattice import build_cycle
from kubernetes_tpu.ops.waves import assign_waves
from kubernetes_tpu.sched.cycle import UNSCHEDULABLE_TAINT_KEY
from kubernetes_tpu.state.dims import Dims
from kubernetes_tpu.state.encode import Encoder

from scenarios import PLACED, SCENARIOS, nodename_pin_mid_burst
from test_golden import oracle_fits, rand_node, rand_pod


def _encode(nodes, existing, pending):
    enc = Encoder()
    enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
    enc.vocabs.label_vals.intern("")
    tables, ex, pe, d = enc.encode_cluster(nodes, existing, pending, None)
    uk = jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY))
    ev = jnp.int32(enc.vocabs.label_vals.get(""))
    return tables, ex, pe, uk, ev, d


@functools.partial(jax.jit, static_argnums=(0, 6))
def _run_impl(engine, tables, ex, pe, uk, ev, D):
    cyc = build_cycle(tables, ex, uk, ev, D)
    init = initial_state(tables, cyc)
    if engine == "scan":
        return assign_batch(tables, cyc, pe, init), None
    return assign_waves(tables, cyc, pe, init, return_waves=True)


def _run(engine, tables, ex, pe, uk, ev, D):
    return _run_impl(engine, jax.device_put(tables), jax.device_put(ex),
                     jax.device_put(pe), uk, ev, D)


def test_waves_match_scan_homogeneous():
    """Identical pods on identical nodes: both engines must produce the same
    round-robin placement (distinct nodes within a wave, refilled in order)."""
    nodes = [Node(name=f"n{i}",
                  allocatable=Resources.make(cpu="4", memory="8Gi", pods=110))
             for i in range(8)]
    pods = [Pod(name=f"p{i}",
                requests=Resources.make(cpu="500m", memory="512Mi"),
                creation_index=i)
            for i in range(24)]
    tables, ex, pe, uk, ev, d = _encode(nodes, [], pods)
    scan_res, _ = _run("scan", tables, ex, pe, uk, ev, d.D)
    wave_res, _ = _run("waves", tables, ex, pe, uk, ev, d.D)
    np.testing.assert_array_equal(
        np.asarray(wave_res.node), np.asarray(scan_res.node))
    np.testing.assert_array_equal(
        np.asarray(wave_res.state.used), np.asarray(scan_res.state.used))


def test_singleton_high_class_index_ties_match_scan():
    """A single pending class must use tie-rotation offset 0 even when its
    interned class INDEX is nonzero (other classes exist from bound pods):
    the offset keys on queue rank within the batch, not the global class id
    (code-review regression — uniform nodes, all scores tied, waves must
    pick the scan's lowest-index node)."""
    nodes = [Node(name=f"n{i}",
                  allocatable=Resources.make(cpu="8", memory="16Gi",
                                             pods=110))
             for i in range(8)]
    # two bound pods with distinct specs intern classes 0 and 1 first
    existing = [
        Pod(name="e0", requests=Resources.make(cpu="1", memory="1Gi"),
            node_name="n5", creation_index=0),
        Pod(name="e1", requests=Resources.make(cpu="2", memory="2Gi"),
            node_name="n6", creation_index=1),
    ]
    pending = [Pod(name="p", labels={"fresh": "yes"},
                   requests=Resources.make(cpu="500m", memory="512Mi"),
                   creation_index=10)]
    tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)
    res_w, _ = _run("waves", tables, ex, pe, uk, ev, d.D)
    res_s, _ = _run("scan", tables, ex, pe, uk, ev, d.D)
    assert int(np.asarray(res_w.node)[0]) == int(np.asarray(res_s.node)[0])


def test_decisive_score_gap_not_steamrolled_by_spreading():
    """EngineConfig.w_window: a node whose score trails the class max by
    more than the window must not receive same-wave spillover while the
    preferred node still has capacity (code-review/verify regression: a
    10,000-point NodePreferAvoidPods gap used to be ignored because the
    class admitted one pod per node on its top-r feasible nodes)."""
    import dataclasses

    from kubernetes_tpu.framework.plugins import NodePreferAvoidPods
    from kubernetes_tpu.sched.cycle import _schedule_batch, snapshot_with_keys
    from kubernetes_tpu.state.cache import SchedulerCache
    from kubernetes_tpu.state.encode import Encoder

    cache = SchedulerCache()
    enc = Encoder()
    avoided = dataclasses.replace(
        Node(name="avoided",
             allocatable=Resources.make(cpu="8", memory="16Gi", pods=110)),
        prefer_avoid_pods=True)
    cache.add_node(avoided)
    cache.add_node(Node(
        name="normal",
        allocatable=Resources.make(cpu="8", memory="16Gi", pods=110)))
    pods = [Pod(name=f"p{i}",
                requests=Resources.make(cpu="100m", memory="64Mi"),
                creation_index=i) for i in range(6)]
    snap, keys = snapshot_with_keys(cache, enc, pods, None)
    res = _schedule_batch(snap.tables, snap.pending, keys, snap.dims.D,
                          snap.existing,
                          extra_plugins=(NodePreferAvoidPods(),),
                          extra_weights=(100.0,))
    node_idx = np.asarray(jax.device_get(res.node))[:6]
    names = [snap.node_order[i] for i in node_idx]
    assert names == ["normal"] * 6, names


def test_waves_respect_priority_tiers():
    """A higher-priority pod must win the last slot on a nearly-full node
    (activeQ order: priority desc — scheduling_queue.go:119-138)."""
    nodes = [Node(name="n0",
                  allocatable=Resources.make(cpu="1", memory="1Gi", pods=10))]
    low = Pod(name="low", requests=Resources.make(cpu="1", memory="1Gi"),
              priority=0, creation_index=0)
    high = Pod(name="high", requests=Resources.make(cpu="1", memory="1Gi"),
               priority=10, creation_index=1)
    tables, ex, pe, uk, ev, d = _encode(nodes, [], [low, high])
    res, _ = _run("waves", tables, ex, pe, uk, ev, d.D)
    node = np.asarray(res.node)
    assert node[1] == 0, "high-priority pod must be placed"
    assert node[0] == -1, "low-priority pod must lose the contended slot"


def test_waves_handle_extreme_negative_priorities():
    """Priorities below any sentinel (e.g. INT32_MIN-adjacent PriorityClass
    values) must still tier and schedule — regression for the -2^30 sentinel
    collision that spun the wave loop to its cap."""
    nodes = [Node(name=f"n{i}",
                  allocatable=Resources.make(cpu="4", memory="8Gi", pods=10))
             for i in range(2)]
    pods = [Pod(name=f"p{i}",
                requests=Resources.make(cpu="100m", memory="64Mi"),
                priority=-(2**31) + i, creation_index=i)
            for i in range(3)]
    tables, ex, pe, uk, ev, d = _encode(nodes, [], pods)
    res, waves = _run("waves", tables, ex, pe, uk, ev, d.D)
    node = np.asarray(res.node)[:3]
    assert (node >= 0).all(), f"negative-priority pods unscheduled: {node}"
    # tiers are per distinct priority here, so 3 pods = 3 waves, not 2P+2
    assert int(np.asarray(waves).max()) < 6


def _random_cluster(seed):
    rng = random.Random(1000 + seed)
    n_nodes = rng.randint(4, 8)
    nodes = [rand_node(rng, i) for i in range(n_nodes)]
    existing = [
        rand_pod(rng, 100 + i, bound_to=rng.choice(nodes).name)
        for i in range(rng.randint(0, 6))
    ]
    pending = [rand_pod(rng, i) for i in range(rng.randint(8, 16))]
    return nodes, existing, pending


@pytest.mark.parametrize("seed", [*range(8), *SCENARIOS])
def test_wave_replay_is_valid_greedy_execution(seed):
    """Randomized clusters (affinity, anti-affinity, spread, taints, ports)
    and the named replica-burst scenarios (tests/scenarios.py): replaying
    the wave output pod-by-pod in (wave, queue-order) must pass the full
    oracle predicate chain at every step; where every greedy execution of a
    scenario places the same number of pods, so many are placed."""
    nodes, existing, pending = SCENARIOS[seed]() if seed in SCENARIOS \
        else _random_cluster(seed)

    tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)
    res, waves = _run("waves", tables, ex, pe, uk, ev, d.D)
    node_idx = np.asarray(res.node)[: len(pending)]
    wave_idx = np.asarray(waves)[: len(pending)]

    placed = [
        (int(wave_idx[i]), -pending[i].priority, pending[i].creation_index, i)
        for i in range(len(pending))
        if node_idx[i] >= 0
    ]
    placed.sort()
    world = list(existing)
    for _, _, _, i in placed:
        node = nodes[int(node_idx[i])]
        assert oracle_fits(pending[i], node, nodes, world), (
            f"seed={seed}: pod {pending[i].name} placed on {node.name} "
            f"in wave {wave_idx[i]} violates the oracle at replay time\n"
            f"pod={pending[i]}"
        )
        world.append(dataclasses.replace(pending[i], node_name=node.name))
    if seed in PLACED:
        assert len(placed) == PLACED[seed]
    elif seed == "capacity-exhaustion":
        assert 0 < len(placed) < len(pending)


@pytest.mark.parametrize("has_node_name, engine", [
    (False, "waves"),
    (True, "scan"),     # spec.nodeName is per pod: the literal scan
])
def test_plan_engine_table(has_node_name, engine):
    """`plan_engine` is the one place a wave's program is chosen, from the
    one fact the snapshot's dims carry about the batch."""
    from kubernetes_tpu.sched.cycle import plan_engine
    from kubernetes_tpu.state.cache import SchedulerCache

    assert plan_engine(has_node_name) == engine
    cache = SchedulerCache()
    cache.add_node(Node(name="n0", allocatable=Resources.make(
        cpu="4", memory="8Gi", pods=10)))
    pods = [Pod(name=f"p{i}", creation_index=i,
                node_name="n0" if has_node_name and i == 1 else "",
                requests=Resources.make(cpu="100m", memory="64Mi"))
            for i in range(3)]
    snap = cache.snapshot(Encoder(), pods, None,
                          extra_intern=(UNSCHEDULABLE_TAINT_KEY,))
    assert plan_engine(snap.dims.has_node_name) == engine


def test_nodename_pins_mid_burst_land_through_the_scan():
    """spec.nodeName pods in the middle of a replica burst: the batch is
    the scan's (`plan_engine`), `_schedule_batch` dispatches it there, the
    pinned pods land on their node and every other pod validly."""
    from kubernetes_tpu.sched.cycle import _schedule_batch, plan_engine

    nodes, existing, pending = nodename_pin_mid_burst()
    tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)
    assert d.has_node_name and plan_engine(d.has_node_name) == "scan"
    res = _schedule_batch(jax.device_put(tables), jax.device_put(pe),
                          (uk, ev), d.D, jax.device_put(ex),
                          has_node_name=d.has_node_name)
    node_idx = np.asarray(res.node)[: len(pending)]
    scan_res, _ = _run("scan", tables, ex, pe, uk, ev, d.D)
    np.testing.assert_array_equal(
        node_idx, np.asarray(scan_res.node)[: len(pending)])
    assert node_idx[3] == 2 and node_idx[4] == 2, "pinned pods land on n2"
    assert (node_idx >= 0).all()
    world = list(existing)
    for i, pod in enumerate(pending):   # one priority: queue order
        node = nodes[int(node_idx[i])]
        assert oracle_fits(pod, node, nodes, world), (pod.name, node.name)
        world.append(dataclasses.replace(pod, node_name=node.name))


@pytest.mark.parametrize("seed", range(4))
def test_waves_and_scan_agree_on_feasibility_of_singletons(seed):
    """With a single pending pod there is no interleaving freedom: waves and
    scan must agree exactly (placement and feasibility)."""
    rng = random.Random(2000 + seed)
    nodes = [rand_node(rng, i) for i in range(5)]
    existing = [rand_pod(rng, 100 + i, bound_to=rng.choice(nodes).name)
                for i in range(3)]
    for j in range(6):
        pod = rand_pod(rng, j)
        tables, ex, pe, uk, ev, d = _encode(nodes, existing, [pod])
        s, _ = _run("scan", tables, ex, pe, uk, ev, d.D)
        w, _ = _run("waves", tables, ex, pe, uk, ev, d.D)
        assert int(np.asarray(w.node)[0]) == int(np.asarray(s.node)[0]), (
            f"seed={seed} pod {j}: waves={int(np.asarray(w.node)[0])} "
            f"scan={int(np.asarray(s.node)[0])}"
        )


def test_wave_replay_mid_scale_100_nodes_1k_pods():
    """Mid-scale soundness (VERDICT r2 weak #4): the interaction graph, domain
    quotas, and cumulative resource resolution are exactly the mechanisms
    whose bugs appear under DENSITY — dozens of classes contending per node —
    not at n=8. One seeded 100×1000 flagship replay covers that regime: every
    placement must pass the oracle predicate chain at replay time."""
    from kubernetes_tpu.models.workloads import flagship_pods, make_nodes

    nodes = make_nodes(100, zones=4, racks_per_zone=5)
    pending = flagship_pods(1000, groups=24)
    tables, ex, pe, uk, ev, d = _encode(nodes, [], pending)
    res, waves = _run("waves", tables, ex, pe, uk, ev, d.D)
    node_idx = np.asarray(res.node)[: len(pending)]
    wave_idx = np.asarray(waves)[: len(pending)]
    n_placed = int((node_idx >= 0).sum())
    assert n_placed > 300, f"only {n_placed}/1000 placed at mid-scale"

    placed = [
        (int(wave_idx[i]), -pending[i].priority, pending[i].creation_index, i)
        for i in range(len(pending)) if node_idx[i] >= 0
    ]
    placed.sort()
    world = []
    # replay with incremental per-node usage bookkeeping (the full
    # oracle_fits re-aggregates per step; at 1k pods keep it O(P·terms))
    for _, _, _, i in placed:
        node = nodes[int(node_idx[i])]
        assert oracle_fits(pending[i], node, nodes, world), (
            f"pod {pending[i].name} on {node.name} wave {wave_idx[i]} "
            f"violates the oracle at replay time")
        world.append(dataclasses.replace(pending[i], node_name=node.name))


def test_waves_engine_beats_scan_floor():
    """CI guard (VERDICT r2 weak #8): the wave engine's win over the
    sequential scan must not silently regress. At a fixed CPU shape the
    waves engine must stay ≥2× faster than the scan (the measured gap is
    ~10-14×; a true regression to scan-level shows ~1×, so 2× discriminates
    while tolerating shared-suite CPU noise)."""
    import time

    from kubernetes_tpu.models.workloads import flagship_pods, make_nodes

    nodes = make_nodes(64, zones=4, racks_per_zone=4)
    pending = flagship_pods(512, groups=12)
    tables, ex, pe, uk, ev, d = _encode(nodes, [], pending)

    def timed(engine):
        _run(engine, tables, ex, pe, uk, ev, d.D)  # compile
        t0 = time.perf_counter()
        res, _ = _run(engine, tables, ex, pe, uk, ev, d.D)
        jax.block_until_ready(res.node)
        return time.perf_counter() - t0

    t_waves = min(timed("waves") for _ in range(5))
    t_scan = min(timed("scan") for _ in range(2))
    assert t_waves * 2 < t_scan, (
        f"waves engine no longer beats scan 2x: waves={t_waves:.3f}s "
        f"scan={t_scan:.3f}s")


def test_class_axis_tiling_bit_identical(monkeypatch):
    """Long-context tiling: with many DISTINCT pod specs the per-wave dense
    evaluation runs blockwise over the class axis (lax.map) — results must be
    bit-identical to the un-tiled vmap."""
    from kubernetes_tpu.ops import waves as waves_mod

    rng = random.Random(42)
    nodes = [rand_node(rng, i) for i in range(8)]
    # distinct creation labels force ~40 distinct classes
    pending = []
    for i in range(40):
        p = rand_pod(rng, i)
        p.labels = {**p.labels, "uniq": f"u{i}"}
        pending.append(p)
    tables, ex, pe, uk, ev, d = _encode(nodes, [], pending)

    res_ref, _ = _run("waves", tables, ex, pe, uk, ev, d.D)
    ref = np.asarray(res_ref.node)

    monkeypatch.setattr(waves_mod, "_CLASS_BLOCK", 8)  # force ~5 blocks
    jax.clear_caches()
    try:
        res_tiled, _ = _run("waves", tables, ex, pe, uk, ev, d.D)
        np.testing.assert_array_equal(np.asarray(res_tiled.node), ref)
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def _affinity_cluster(seed):
    """Random cluster whose pending pods carry every kind of pod-affinity
    term, several replicas a spec (so classes hold runs) and bound pods
    (so the counts are not zero)."""
    rng = random.Random(seed)
    nodes = [rand_node(rng, i) for i in range(10)]
    existing = [rand_pod(rng, 500 + i, bound_to=rng.choice(nodes).name)
                for i in range(20)]
    pending = []
    for i in range(12):
        p = rand_pod(rng, i)
        for j in range(3):
            pending.append(dataclasses.replace(
                p, name=f"{p.name}-{j}", creation_index=10 * i + j))
    return nodes, existing, pending


@pytest.mark.parametrize("seed", range(3))
def test_waves_with_table_equal_waves_per_row(seed, monkeypatch):
    """The round's [S, N] in-domain count table against every class
    aggregating its own slots (the few-rows parameterisation, forced onto
    the waves round): placements, admission waves and final counts
    identical."""
    from kubernetes_tpu.ops import waves as waves_mod

    nodes, existing, pending = _affinity_cluster(seed)
    tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)
    assert d.affinity_agg("waves") == "term"
    res_t, waves_t = _run("waves", tables, ex, pe, uk, ev, d.D)

    monkeypatch.setattr(waves_mod, "state_affinity_table",
                        lambda *a, **k: None)
    jax.clear_caches()
    try:
        res_r, waves_r = _run("waves", tables, ex, pe, uk, ev, d.D)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for a, b in ((res_t.node, res_r.node), (waves_t, waves_r),
                 (res_t.state.CNT, res_r.state.CNT),
                 (res_t.state.used, res_r.state.used)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(res_t.node) >= 0).any()


def test_escape_cap_counts_what_the_predicate_counts():
    """The first-pod escape's total counts matching pods on nodes that CARRY
    the term's key (predicates.go:1436-1440 through the topology-pair map,
    which has no entry for a keyless node). A matching pod on a keyless node
    therefore leaves the escape open, and the cap must hold the class to one
    pod that wave: its followers have to land in the first one's zone."""
    zone = "topology.kubernetes.io/zone"
    nodes = [Node(name=f"n{i}", labels={zone: f"z{i % 3}"},
                  allocatable=Resources.make(cpu="4", memory="8Gi", pods=110))
             for i in range(6)]
    nodes.append(Node(name="bare", allocatable=Resources.make(
        cpu="4", memory="8Gi", pods=110)))
    from kubernetes_tpu.api.types import (
        Affinity, LabelSelector, PodAffinityTerm)
    together = Affinity(pod_required=(PodAffinityTerm(
        selector=LabelSelector.of(match_labels={"app": "herd"}),
        topology_key=zone),))
    mk = lambda name, i, node="": Pod(
        name=name, labels={"app": "herd"}, affinity=together,
        requests=Resources.make(cpu="1", memory="1Gi"), node_name=node,
        creation_index=i)
    tables, ex, pe, uk, ev, d = _encode(
        nodes, [mk("lost", 0, "bare")], [mk(f"p{i}", 1 + i) for i in range(4)])
    res, waves = _run("waves", tables, ex, pe, uk, ev, d.D)
    node = np.asarray(res.node)[:4]
    assert (node >= 0).all() and (node < 6).all()
    assert len({int(n) % 3 for n in node}) == 1, node
    w = np.asarray(waves)[:4]
    assert (w == w.min()).sum() == 1, w


# ---------------------------------------------------------------------------
# PR 38: the admission's order and quotas ride sorts (ops/waves.py). Two
# rungs: `assign_waves` end to end against arrays RECORDED from the parent
# commit, and the new order / quota pieces against the parent's gather /
# scatter forms, kept here verbatim as the plain reference.
# ---------------------------------------------------------------------------

_RECORDED = "waves_parent_e8e4f80.npz"   # from commit e8e4f80 (PR 37)


def _recorded_case(name):
    """Flagship-shaped inputs (hard zone spread on every group, host
    anti-affinity on a third, required in-zone affinity to a partner on a
    third, three priorities, four request tiers), built without randomness."""
    from kubernetes_tpu.models.workloads import (ZONE, flagship_pods,
                                                 make_nodes)

    def renamed(pods, prefix, at):
        return [dataclasses.replace(p, name=f"{prefix}-{p.name}",
                                    creation_index=at + p.creation_index)
                for p in pods]

    if name == "flagship-100x1000":
        return (make_nodes(100, zones=4, racks_per_zone=5), [],
                flagship_pods(1000, groups=24))
    if name == "bound-64x360":
        # pods already bound (so counts, quotas and the escape are not
        # zero) and four nodes that carry no zone label (dom = -1)
        nodes = make_nodes(64, zones=4, racks_per_zone=4)
        for i in (5, 22, 39, 56):
            nodes[i] = dataclasses.replace(nodes[i], labels={
                k: v for k, v in nodes[i].labels.items() if k != ZONE})
        bound = [dataclasses.replace(p, node_name=f"node-{(7 * i) % 64}")
                 for i, p in enumerate(flagship_pods(240, groups=12))]
        return nodes, bound, renamed(flagship_pods(360, groups=12), "w", 1000)
    if name == "tight-16x300":
        # far more pods than room: contention losers retry, runs fail
        return (make_nodes(16, zones=4, racks_per_zone=2, cpu="4",
                           memory="8Gi", pods=20), [],
                flagship_pods(300, groups=9))
    raise KeyError(name)


_RECORDED_CASES = ("flagship-100x1000", "bound-64x360", "tight-16x300")


def _record_parent(path):
    """How the file was made: this module run as a script with the PARENT's
    package first on the path, `cd <git archive e8e4f80> && PYTHONPATH=.
    python <this tree>/tests/test_waves.py record`."""
    out = {}
    for name in _RECORDED_CASES:
        nodes, existing, pending = _recorded_case(name)
        tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)
        res, waves = _run("waves", tables, ex, pe, uk, ev, d.D)
        out[name + "/node"] = np.asarray(res.node)
        out[name + "/waves"] = np.asarray(waves)
        print(name, int((out[name + "/node"] >= 0).sum()), "placed of",
              len(pending), "in", int(out[name + "/waves"].max()) + 1, "waves")
    np.savez_compressed(path, **out)


@pytest.mark.parametrize("name", _RECORDED_CASES)
def test_assign_waves_equals_the_parents_recorded_placements(name):
    """`node` and the returned `waves`, element for element, against what
    the parent commit's gather / scatter form placed on the same inputs."""
    import os
    ref = np.load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               _RECORDED))
    nodes, existing, pending = _recorded_case(name)
    tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)
    res, waves = _run("waves", tables, ex, pe, uk, ev, d.D)
    np.testing.assert_array_equal(np.asarray(res.node), ref[name + "/node"])
    np.testing.assert_array_equal(np.asarray(waves), ref[name + "/waves"])
    assert (ref[name + "/node"] >= 0).sum() > 100


# --- the parent's forms, verbatim (ops/waves.py at e8e4f80: `slot_quota`
# :165-183 with its closure's names made arguments, the score order :393-405)
def _parent_slot_quota(order_row, dom, D, active, quota_d):
    N = dom.shape[0]
    dom_sorted = dom[order_row]                   # [N] score-desc order
    dsafe = jnp.where(dom_sorted >= 0, dom_sorted, D)
    gidx = jnp.arange(N, dtype=jnp.int32)
    grp = jnp.argsort(dsafe, stable=True)         # grouped order
    dom_g = dsafe[grp]
    start = jnp.full((D + 1,), N, jnp.int32).at[dom_g].min(gidx)
    rank_g = gidx - start[dom_g]
    rank_in_dom = jnp.zeros((N,), jnp.int32).at[grp].set(rank_g)
    return ~active | (rank_in_dom < quota_d[dsafe])


def _parent_admission(score, adm_mask, offs, r, slots, D):
    """→ (order_n, allowed in score order, A). slots: (dom [SC, N],
    active [SC], quota_d [SC, D + 1]) per constraint slot."""
    SC, N = score.shape
    rot = (jnp.arange(N, dtype=jnp.int32)[None, :]
           + offs[:, None]) % N                          # [SC, N]
    score_rot = jnp.take_along_axis(score, rot, axis=1)
    order_rot = jnp.argsort(-score_rot, axis=1)
    order_n = jnp.take_along_axis(rot, order_rot, axis=1)  # [SC, N]
    feas_sorted = jnp.take_along_axis(adm_mask, order_n, axis=1)
    allowed = feas_sorted
    for dom, active, quota_d in slots:
        allowed = allowed & jax.vmap(
            lambda o, dm, a, q: _parent_slot_quota(o, dm, D, a, q))(
                order_n, dom, active, quota_d)
    grank = jnp.cumsum(allowed.astype(jnp.int32), axis=1) - 1
    adm_sorted = allowed & (grank < r[:, None])
    A = jnp.zeros((SC, N), bool).at[
        jnp.arange(SC)[:, None], order_n].set(adm_sorted)
    return order_n, allowed, A


def _sorted_admission(score, adm_mask, offs, r, slots, D):
    """The same three, composed from ops/waves.py's pieces the way
    `assign_waves.body` and `_domain_quota_pass` compose them."""
    from kubernetes_tpu.ops import waves as W

    SC, N = score.shape
    neg_score = -score
    rot_pos = (jnp.arange(N, dtype=jnp.int32)[None, :] - offs[:, None]) % N
    allowed_n = adm_mask
    for dom, active, quota_d in slots:
        cap = jnp.take_along_axis(quota_d, jnp.where(dom >= 0, dom, D), axis=1)
        allowed_n = allowed_n & (~active[:, None] | jax.vmap(
            lambda ns, rp, o, dm, q: W._within_quota(ns, rp, o, dm, D, q))(
                neg_score, rot_pos, offs, dom, cap))
    order_n, allowed = W._score_order(neg_score, rot_pos, offs, allowed_n)
    grank = jnp.cumsum(allowed.astype(jnp.int32), axis=1) - 1
    return order_n, allowed, W._to_nodes(order_n, allowed & (grank < r[:, None]))


_I32_MAX = int(np.iinfo(np.int32).max)
_ADMISSION_SHAPES = {
    # name: (N, D, how the domains are drawn)
    "zone-like-D16": (96, 16, "mod"),
    "rack-like-D320": (640, 320, "mod"),
    "hostname-D-is-N": (80, 80, "perm"),
}
_ADMISSION_TWISTS = ("plain", "invalid-nodes", "inactive-slot",
                     "all-scores-equal", "inf-rows", "quota-0-1-max")


def _admission_inputs(seed, shape, twist):
    N, D, how = _ADMISSION_SHAPES[shape]
    SC = 6
    rng = np.random.default_rng([seed, N, len(twist)])
    # few distinct scores: ties everywhere, so the rotation decides often
    score = rng.integers(0, 4, (SC, N)).astype(np.float32)
    if twist == "all-scores-equal":
        score[:] = 7.0
    mask = rng.random((SC, N)) < 0.8
    if twist == "inf-rows":
        mask[1] = False                   # a class feasible nowhere
        mask[2, : N // 2] = False
        score[3, ::3] = -np.inf           # -inf on unmasked nodes too
    score = np.where(mask, score, -np.inf).astype(np.float32)
    best = np.where(mask, score, -np.inf).max(axis=1, keepdims=True)
    adm_mask = mask & (score >= best - 1.0)

    def domains():
        if how == "perm":
            return np.stack([rng.permutation(N) for _ in range(SC)])
        return (np.arange(N)[None, :] * 7 + rng.integers(0, D, (SC, 1))) % D

    slots = []
    for kind in ("spread", "anti"):
        dom = domains().astype(np.int32)
        if twist == "invalid-nodes":
            dom[rng.random((SC, N)) < 0.2] = -1
            dom[4] = -1                   # a key no node carries
        active = np.ones((SC,), bool)
        if twist == "inactive-slot":
            active[rng.random(SC) < 0.5] = False
            active[0] = False
        if kind == "anti":
            quota_d = np.ones((SC, D + 1), np.int32)
        elif twist == "quota-0-1-max":
            quota_d = rng.choice(np.array([0, 1, _I32_MAX], np.int32),
                                 (SC, D + 1))
        else:
            quota_d = rng.integers(0, 4, (SC, D + 1)).astype(np.int32)
        slots.append((jnp.asarray(dom), jnp.asarray(active),
                      jnp.asarray(quota_d)))
    offs = (rng.permutation(SC) * 97 % N).astype(np.int32)
    r = rng.integers(0, N // 3, (SC,)).astype(np.int32)
    return (jnp.asarray(score), jnp.asarray(adm_mask), jnp.asarray(offs),
            jnp.asarray(r), slots, D)


@pytest.mark.parametrize("twist", _ADMISSION_TWISTS)
@pytest.mark.parametrize("shape", list(_ADMISSION_SHAPES))
@pytest.mark.parametrize("seed", range(2))
def test_sorted_admission_equals_the_parents_gather_form(seed, shape, twist):
    """The score order, `allowed` in it and the admission matrix `A` from
    the sort-borne pieces, bit for bit against the parent's element gathers
    and scatters through the permutation."""
    args = _admission_inputs(seed, shape, twist)
    want = jax.jit(_parent_admission, static_argnums=5)(*args)
    got = jax.jit(_sorted_admission, static_argnums=5)(*args)
    for name, w, g in zip(("order_n", "allowed", "A"), want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    if twist in ("plain", "all-scores-equal"):
        assert np.asarray(want[2]).any()


def _file_line(eqn):
    from jax._src import source_info_util

    fr = source_info_util.user_frame(eqn.source_info.traceback)
    return f"{fr.file_name.rsplit('/', 1)[-1]}:{fr.start_line}" if fr else "?"


def _big_indexed_ops(jaxpr, floor, in_loop=False, where=_file_line):
    """(primitive, where(eqn)) of every gather / scatter* equation inside a
    `while` body whose index operand holds `floor` or more index vectors;
    `where` says file:line of the innermost frame of the program's own."""
    import math

    found = []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if in_loop and (prim == "gather" or prim.startswith("scatter")):
            idx = eqn.invars[1].aval.shape
            if math.prod(idx[:-1]) >= floor:
                found.append((prim, where(eqn)))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _big_indexed_ops(
                        sub, floor, in_loop or prim == "while", where)
    return found


# what is left: nothing. (PR 38 left one, the spread family's cap said of
# each node by a gather from the [D + 1] table of domains; since PR 43 the
# cap is computed per node from the round's per-node counts.)
_SC_BY_N_INDEXED_LEFT = 0


def test_no_sc_by_n_array_is_fetched_through_a_permutation():
    """Structural guard: in the compiled round (the `while` body of
    `assign_waves`) count the gather / scatter equations that ops/waves.py
    itself writes with SC x N or more index vectors. The parent (e8e4f80)
    had 21 (`/PERF.md` section 6, PR 38: ~10 ns an element on the chip, 3.3 ms
    each at [64, 5120]). What is left is listed here by name; a later PR
    that fetches an [SC, N] array through a permutation again meets this
    test and not a ledger line."""
    nodes, existing, pending = _recorded_case("bound-64x360")
    tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)
    assert d.P < d.SC * d.N     # so that pod-axis lookups are not counted

    def f(tables, ex, pe, uk, ev):
        cyc = build_cycle(tables, ex, uk, ev, d.D)
        return assign_waves(tables, cyc, pe, initial_state(tables, cyc),
                            return_waves=True)

    ops = _big_indexed_ops(jax.make_jaxpr(f)(tables, ex, pe, uk, ev).jaxpr,
                           d.SC * d.N)
    ours = sorted(o for o in ops if o[1].startswith("waves.py"))
    assert len(ours) == _SC_BY_N_INDEXED_LEFT, ours


def _round_jaxpr(case):
    nodes, existing, pending = _recorded_case(case)
    tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)

    def f(tables, ex, pe, uk, ev):
        cyc = build_cycle(tables, ex, uk, ev, d.D)
        return assign_waves(tables, cyc, pe, initial_state(tables, cyc),
                            return_waves=True)

    return jax.make_jaxpr(f)(tables, ex, pe, uk, ev).jaxpr, d


def _asked_by(eqn):
    """The functions of ops/interpod.py, ops/topospread.py and ops/scores.py
    on the equation's traceback, innermost first, '' where none of the files
    is on it: WHO asked for the indexed operation."""
    from jax._src import source_info_util

    return ">".join(
        fr.function_name
        for fr in source_info_util.user_frames(eqn.source_info.traceback)
        if fr.file_name.endswith(("ops/interpod.py", "ops/topospread.py",
                                  "ops/scores.py")))


def test_no_s_by_n_table_is_summed_through_a_scatter_in_a_product_round(
        monkeypatch):
    """Structural guard (ISSUE 42, ISSUE 43): where the program's Dims choose
    the product (state/dims.py domain_sum), the compiled round (the `while`
    body of `assign_waves`) holds NO scatter-add and NO gather with SC x N
    or more index vectors that ops/interpod.py's in-domain sum,
    ops/topospread.py's spread counts or ops/scores.py asked for: the
    round's count table, `hold` and the weights are one product against the
    cycle's same-domain matrices, and topology spread's eligible-masked
    counts of every (class, slot) another. PR 42's parent had six for the
    tables (41 ms of the flagship cycle's 82, `/PERF.md` section 6, PR 42);
    PR 43's had three more `domain_agg` scatter-adds for spread (the Filter
    row's, the soft score's, the quota's) and a gather back each (27 ms of a
    cycle's 42, section 6, PR 43): the minimum over a key's DOMAINS that
    they read is the minimum over the nodes whose domain is eligible, which
    the per-node sum does give. Once the rule is made to fall back the same
    count finds exactly two pairs: the tables' stacked sum and spread's ONE
    sum a round, shared by its three readers."""
    from kubernetes_tpu.state import dims as dims_mod

    def asked(jaxpr, floor):
        ops = _big_indexed_ops(jaxpr, floor, where=_asked_by)
        return sorted(o for o in ops if o[1])

    jaxpr, d = _round_jaxpr("bound-64x360")
    assert d.domain_sum("waves") == "product"
    assert d.affinity_agg("waves") == "term"
    floor = min(d.S, d.SC) * d.N
    assert asked(jaxpr, floor) == []

    monkeypatch.setattr(dims_mod, "DOMAIN_SUM_MAX_BYTES", 0)
    assert d.domain_sum("waves") == "scatter"
    jaxpr, _ = _round_jaxpr("bound-64x360")
    left = asked(jaxpr, floor)
    assert [w for p, w in left if p == "scatter-add"] == [
        "domain_agg>in_domain_sums>eligible_domain_counts>spread_counts",
        # the three tables ride ONE stacked sum
        "domain_agg>in_domain_sums>term_domain_counts"], left
    # one gather back each. (Its frames are those of whoever traced
    # `take_along_axis` at that shape first, build_cycle's ELN for spread's:
    # jax caches the inner jit's jaxpr.)
    assert len(left) == 4 and all(
        w.startswith("in_domain_sums>") for p, w in left if p == "gather"), left


def _spread_cluster(seed):
    """Random cluster whose pending pods carry HARD spread (zone or hostname,
    maxSkew 1-2) and SOFT spread over the other key, some behind a node
    selector (so eligibility bites), several replicas a spec; a fifth of
    the nodes lack the zone label; bound pods matching the selectors."""
    from kubernetes_tpu.api.types import (
        LabelSelector, TopologySpreadConstraint, UnsatisfiableAction)

    zone, host = "topology.kubernetes.io/zone", "kubernetes.io/hostname"
    rng = random.Random(1000 + seed)
    nodes = []
    for i in range(14):
        labels = {host: f"n{i}", "pool": rng.choice("ab")}
        if rng.random() < 0.8:
            labels[zone] = f"z{rng.randrange(3)}"
        nodes.append(Node(name=f"n{i}", labels=labels,
                          allocatable=Resources.make(cpu="8", memory="16Gi",
                                                     pods=30)))
    apps = ["web", "db", "cache"]

    def pod(name, app, i, node="", spread=(), sel=None):
        return Pod(name=name, labels={"app": app}, node_name=node,
                   node_selector=sel or {}, topology_spread=spread,
                   requests=Resources.make(cpu="250m", memory="256Mi"),
                   creation_index=i)

    existing = [pod(f"e{i}", rng.choice(apps), 500 + i,
                    node=rng.choice(nodes).name) for i in range(24)]
    pending = []
    for g in range(8):
        app = apps[g % 3]
        hard_key, soft_key = (zone, host) if g % 3 else (host, zone)
        spread = (
            TopologySpreadConstraint(
                max_skew=rng.randint(1, 2), topology_key=hard_key,
                when_unsatisfiable=UnsatisfiableAction.DO_NOT_SCHEDULE,
                selector=LabelSelector.of(match_labels={"app": app})),
            TopologySpreadConstraint(
                max_skew=1, topology_key=soft_key,
                when_unsatisfiable=UnsatisfiableAction.SCHEDULE_ANYWAY,
                selector=LabelSelector.of(
                    match_labels={"app": rng.choice(apps)})))
        sel = {"pool": "a"} if g % 4 == 1 else None
        for j in range(5):
            pending.append(pod(f"g{g}-{j}", app, 10 * g + j,
                               spread=spread, sel=sel))
    return nodes, existing, pending


_DOMAIN_SUM_CASES = {
    **{f"{cluster}-{seed}": functools.partial(build, seed)
       for cluster, build in (("affinity", _affinity_cluster),
                              ("spread", _spread_cluster))
       for seed in range(3)},
    **SCENARIOS,
}


@pytest.mark.parametrize("case", list(_DOMAIN_SUM_CASES))
def test_waves_with_product_equal_waves_with_scatter(case, monkeypatch):
    """The whole engine under either form of the in-domain sum: placements,
    admission waves and the final counts identical (the sums are the same
    integers), on clusters of pod-affinity terms, on clusters of hard and
    soft topology spread, and on the named replica-burst scenarios
    (tests/scenarios.py: the scatter form runs in no cell, so adversarial
    inputs are what hold it)."""
    from kubernetes_tpu.state import dims as dims_mod

    nodes, existing, pending = _DOMAIN_SUM_CASES[case]()
    tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)
    assert d.domain_sum("waves") == "product"
    res_p, waves_p = _run("waves", tables, ex, pe, uk, ev, d.D)

    monkeypatch.setattr(dims_mod, "DOMAIN_SUM_MAX_BYTES", 0)
    jax.clear_caches()
    try:
        res_s, waves_s = _run("waves", tables, ex, pe, uk, ev, d.D)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for a, b in ((res_p.node, res_s.node), (waves_p, waves_s),
                 (res_p.state.CNT, res_s.state.CNT),
                 (res_p.state.HOLD, res_s.state.HOLD),
                 (res_p.state.WSYM, res_s.state.WSYM),
                 (res_p.state.used, res_s.state.used)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(res_p.node) >= 0).any()
    if case.startswith("spread-"):
        # the constraints bit: more than one wave, and not every pod landed
        # where its class's first pod did
        assert np.asarray(waves_p).max() >= 1
        assert len(set(np.asarray(res_p.node)[:len(pending)].tolist())) > 4


def _quota_inputs(case):
    """(tables, cyc, state, neg_score, rot_pos, offs, adm_mask): a round's
    inputs to `_domain_quota_pass`, taken after one committed wave so that
    the counts are the placed pods' too."""
    from kubernetes_tpu.ops import waves as W
    from kubernetes_tpu.ops.assign import (state_affinity_table,
                                           state_spread_counts)

    if case.startswith("spread-"):
        nodes, existing, pending = _spread_cluster(int(case[-1]))
    else:
        nodes, existing, pending = _recorded_case(case)
    tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)
    tables, ex, pe = (jax.device_put(x) for x in (tables, ex, pe))

    @jax.jit
    def prepare():
        cyc = build_cycle(tables, ex, uk, ev, d.D)
        first = assign_waves(tables, cyc, pe, initial_state(tables, cyc),
                             max_waves=1)
        state = first.state
        SC, N = cyc.static.mask.shape
        table = state_affinity_table(tables, cyc, state, SC)
        spread = state_spread_counts(tables, cyc, state, SC)
        mask, score = W._class_mask_score(tables, cyc, state, table, spread)
        offs = (jnp.arange(SC, dtype=jnp.int32) * 97) % N
        rot_pos = (jnp.arange(N, dtype=jnp.int32)[None, :]
                   - offs[:, None]) % N
        return cyc, state, spread, -score, rot_pos, offs, mask

    return (tables, d) + prepare()


@pytest.mark.parametrize("case", ["bound-64x360", "flagship-100x1000",
                                  "spread-0", "spread-1", "spread-2"])
def test_spread_quota_rows_equal_the_parents_gather_form(case):
    """The round's hard-spread admission rows with the cap said of each node
    from the per-node counts, against the parent's (its own scatter-add, the
    minimum over ELD's domains, the cap gathered from a [D + 1] table): bit
    for bit on every node the class's Filter mask lets through, and
    therefore in `allowed`. A node WITHOUT the key reads another cap than
    the parent's (count 0 against bucket D's sum); where the slot is active
    the Filter row has already refused it, which the case with keyless
    nodes shows."""
    import spread_parent_forms as parent
    from kubernetes_tpu.ops import waves as W

    tables, d, cyc, state, spread, neg_score, rot_pos, offs, mask = \
        _quota_inputs(case)
    classes = tables.classes

    @jax.jit
    def both():
        eld = parent.eligible_domains(cyc.static.node_match, classes,
                                      tables.nodes, d.D)
        rows = parent.spread_quota_rows(
            tables, cyc.static.node_match, cyc.TM, eld, state.CNT, d.D,
            neg_score, rot_pos, offs)
        want = mask & rows.all(axis=1)
        # the new pass with the anti-affinity family switched off: its rows
        # did not change and are ANDed in after spread's
        no_anti = tables._replace(classes=classes._replace(
            anti_terms=jnp.full_like(classes.anti_terms, -1)))
        got = W._domain_quota_pass(no_anti, cyc, spread, mask, neg_score,
                                   rot_pos, offs)
        return want, got, rows

    want, got, rows = jax.tree.map(np.asarray, both())
    np.testing.assert_array_equal(got, want)
    hard = np.asarray((classes.tsc_term >= 0) & classes.tsc_hard
                      & classes.valid[:, None])
    assert hard.any() and np.asarray(mask).any()
    assert (~rows[hard]).any() and want.any()     # the caps bite
    if case != "flagship-100x1000":
        py_nodes = (_spread_cluster(int(case[-1])) if case[0] == "s"
                    else _recorded_case(case))[0]
        assert any("topology.kubernetes.io/zone" not in n.labels
                   for n in py_nodes)


if __name__ == "__main__":
    import os
    import sys
    if sys.argv[1:] == ["record"]:
        _record_parent(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    _RECORDED))
