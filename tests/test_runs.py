"""Run-length-collapsed admission (ops/runs.py) correctness.

The contract is stronger than the wave engine's: placements must be
BIT-EQUAL to the sequential-assume scan (ops/assign.py) — same pods, same
nodes, same order — because the collapse is a pure execution-schedule
optimization, not a different valid greedy execution. Covered here:

  * golden randomized clusters with replica bursts (affinity, anti-affinity,
    spread, taints, ports, volumes — both the closed-form waterfill and the
    self-interaction fallback fire);
  * adversarial runs: self-anti-affinity classes with zero slack,
    port-conflicting replicas, nodeName-pinned pods mid-run, runs straddling
    a capacity-exhaustion boundary, cross-class soft-affinity weight flow
    (the WSYM float-accumulation chain);
  * gang batches (the collapsed engine inside assign_gang's rejection
    loop), a preemption-triggering scheduler drill, and the 8-way virtual
    mesh (sharded vs unsharded bit-equality);
  * the host RunPlan (scan-length bound + collapse telemetry) and the
    self-interaction classifier.
"""

import dataclasses
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Affinity,
    HostPort,
    LabelSelector,
    Node,
    Pod,
    PodAffinityTerm,
    Resources,
)
from kubernetes_tpu.ops.assign import assign_batch, initial_state
from kubernetes_tpu.ops.lattice import build_cycle
from kubernetes_tpu.ops.runs import (
    assign_runs,
    plan_runs,
    self_interaction_vector,
)
from kubernetes_tpu.sched.cycle import UNSCHEDULABLE_TAINT_KEY
from kubernetes_tpu.state.encode import Encoder

from test_golden import rand_node, rand_pod

HOSTNAME = "kubernetes.io/hostname"


def _encode(nodes, existing, pending, base=None):
    enc = Encoder()
    enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
    enc.vocabs.label_vals.intern("")
    tables, ex, pe, d = enc.encode_cluster(nodes, existing, pending, base)
    uk = jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY))
    ev = jnp.int32(enc.vocabs.label_vals.get(""))
    return tables, ex, pe, uk, ev, d


@functools.partial(jax.jit, static_argnums=(0, 6, 7))
def _run_impl(engine, tables, ex, pe, uk, ev, D, rc=0):
    cyc = build_cycle(tables, ex, uk, ev, D)
    init = initial_state(tables, cyc)
    if engine == "scan":
        return assign_batch(tables, cyc, pe, init)
    return assign_runs(tables, cyc, pe, init, rc)


def _rc_of(pe) -> int:
    return plan_runs(np.asarray(pe.cls), np.asarray(pe.priority),
                     np.asarray(pe.creation), np.asarray(pe.valid),
                     np.asarray(pe.node_name_req)).rc


def _run(engine, tables, ex, pe, uk, ev, D):
    rc = _rc_of(pe) if engine == "runs" else 0
    return _run_impl(engine, jax.device_put(tables), jax.device_put(ex),
                     jax.device_put(pe), uk, ev, D, rc)


def _assert_engines_agree(nodes, existing, pending, check_state=True):
    tables, ex, pe, uk, ev, d = _encode(nodes, existing, pending)
    s = _run("scan", tables, ex, pe, uk, ev, d.D)
    r = _run("runs", tables, ex, pe, uk, ev, d.D)
    np.testing.assert_array_equal(np.asarray(r.node), np.asarray(s.node))
    np.testing.assert_array_equal(
        np.asarray(r.feasible), np.asarray(s.feasible))
    if check_state:
        np.testing.assert_array_equal(
            np.asarray(r.state.used), np.asarray(s.state.used))
        np.testing.assert_array_equal(
            np.asarray(r.state.CNT), np.asarray(s.state.CNT))
    return s, r


def _replica(template, i):
    return dataclasses.replace(template, name=f"p{i}", creation_index=i)


# --------------------------------------------------------------------- #
# bit-equality: golden / randomized
# --------------------------------------------------------------------- #


def test_runs_match_scan_homogeneous_spread():
    """One deployment's replicas spreading over uniform nodes — the
    closed-form waterfill's motivating case (all ties, one epoch)."""
    nodes = [Node(name=f"n{i}",
                  allocatable=Resources.make(cpu="4", memory="8Gi", pods=110))
             for i in range(8)]
    pods = [Pod(name=f"p{i}",
                requests=Resources.make(cpu="500m", memory="512Mi"),
                creation_index=i)
            for i in range(24)]
    _assert_engines_agree(nodes, [], pods)


def test_runs_match_scan_capacity_exhaustion_boundary():
    """A run longer than total capacity: the waterfill must exhaust node by
    node and fail the tail exactly where the per-pod scan does."""
    nodes = [Node(name=f"n{i}",
                  allocatable=Resources.make(cpu="2", memory="2Gi", pods=3))
             for i in range(3)]
    big = Pod(name="t", requests=Resources.make(cpu="900m", memory="900Mi"))
    small = Pod(name="s", requests=Resources.make(cpu="300m", memory="100Mi"))
    pods = [_replica(big, i) for i in range(6)] \
        + [_replica(dataclasses.replace(small, creation_index=0), 10 + i)
           for i in range(8)]
    s, _ = _assert_engines_agree(nodes, [], pods)
    node = np.asarray(s.node)[: len(pods)]
    assert (node >= 0).any() and (node < 0).any(), \
        "boundary case must both place and fail pods"


@pytest.mark.parametrize("seed", range(6))
def test_runs_match_scan_golden_random_bursts(seed):
    """Randomized clusters with template-stamped replica bursts: every
    placement (and the committed used/CNT state) bit-equal to the scan,
    whichever inner path (closed form or fallback) each run takes."""
    rng = random.Random(3000 + seed)
    nodes = [rand_node(rng, i) for i in range(rng.randint(3, 7))]
    existing = [rand_pod(rng, 100 + i, bound_to=rng.choice(nodes).name)
                for i in range(rng.randint(0, 5))]
    pending = []
    i = 0
    while len(pending) < 18:
        t = rand_pod(rng, i)
        for _ in range(rng.randint(1, 6)):
            pending.append(_replica(t, i))
            i += 1
    _assert_engines_agree(nodes, existing, pending)


def test_runs_priority_tiers_keep_blocks_contiguous():
    """Two deployments at distinct priorities interleaved by creation: queue
    order re-groups them into two runs; placements must match the scan."""
    nodes = [Node(name=f"n{i}",
                  allocatable=Resources.make(cpu="4", memory="8Gi", pods=10))
             for i in range(4)]
    lo = Pod(name="lo", requests=Resources.make(cpu="250m", memory="256Mi"),
             priority=0)
    hi = Pod(name="hi", requests=Resources.make(cpu="500m", memory="512Mi"),
             priority=5)
    pods = []
    for i in range(12):  # interleaved creation, distinct priorities
        t = hi if i % 2 else lo
        pods.append(dataclasses.replace(t, name=f"p{i}", creation_index=i))
    tables, ex, pe, uk, ev, d = _encode(nodes, [], pods)
    plan = plan_runs(np.asarray(pe.cls), np.asarray(pe.priority),
                     np.asarray(pe.creation), np.asarray(pe.valid),
                     np.asarray(pe.node_name_req))
    assert plan.n_runs == 2, plan
    _assert_engines_agree(nodes, [], pods)


# --------------------------------------------------------------------- #
# adversarial runs (the ISSUE's named cases)
# --------------------------------------------------------------------- #


def test_adversarial_self_anti_affinity_zero_slack():
    """Self-anti-affine replicas (one per hostname domain) with MORE
    replicas than nodes: the class self-interacts → per-pod fallback; the
    overflow replicas must fail exactly like the scan's."""
    nodes = [Node(name=f"n{i}", labels={HOSTNAME: f"n{i}"},
                  allocatable=Resources.make(cpu="8", memory="16Gi",
                                             pods=110))
             for i in range(4)]
    sel = LabelSelector.of(match_labels={"app": "db"})
    t = Pod(name="t", labels={"app": "db"},
            requests=Resources.make(cpu="100m", memory="64Mi"),
            affinity=Affinity(anti_required=(
                PodAffinityTerm(selector=sel, topology_key=HOSTNAME),)))
    pods = [_replica(t, i) for i in range(6)]  # 6 replicas, 4 domains
    s, _ = _assert_engines_agree(nodes, [], pods)
    node = np.asarray(s.node)[:6]
    assert (node >= 0).sum() == 4 and (node < 0).sum() == 2


def test_adversarial_port_conflicting_replicas():
    """Host-port replicas: the port set self-conflicts, capping every node
    at one replica per run — and the overflow fails."""
    nodes = [Node(name=f"n{i}",
                  allocatable=Resources.make(cpu="8", memory="16Gi",
                                             pods=110))
             for i in range(3)]
    t = Pod(name="t", requests=Resources.make(cpu="100m", memory="64Mi"),
            host_ports=(HostPort(8080, "TCP", ""),))
    pods = [_replica(t, i) for i in range(5)]
    s, _ = _assert_engines_agree(nodes, [], pods)
    node = np.asarray(s.node)[:5]
    placed = node[node >= 0]
    assert len(placed) == 3 and len(set(placed.tolist())) == 3
    assert (node < 0).sum() == 2


def test_adversarial_nodename_pinned_mid_run():
    """spec.nodeName pods in the middle of a replica burst: the run splits
    on the pin, pinned stretches take the per-pod fallback, and the whole
    batch still matches the scan bit-for-bit."""
    nodes = [Node(name=f"n{i}",
                  allocatable=Resources.make(cpu="4", memory="8Gi",
                                             pods=110))
             for i in range(4)]
    t = Pod(name="t", requests=Resources.make(cpu="250m", memory="256Mi"))
    pods = []
    for i in range(8):
        p = _replica(t, i)
        if i in (3, 4):  # pinned mid-run
            p = dataclasses.replace(p, node_name="n2")
        pods.append(p)
    tables, ex, pe, uk, ev, d = _encode(nodes, [], pods)
    plan = plan_runs(np.asarray(pe.cls), np.asarray(pe.priority),
                     np.asarray(pe.creation), np.asarray(pe.valid),
                     np.asarray(pe.node_name_req))
    assert plan.n_runs == 3, plan  # unpinned / pinned / unpinned
    s, _ = _assert_engines_agree(nodes, [], pods)
    node = np.asarray(s.node)[:8]
    assert node[3] == 2 and node[4] == 2, "pinned pods must land on n2"


def test_adversarial_cross_class_soft_affinity_weight_flow():
    """A run with preferred affinity toward ANOTHER class is still
    self-interaction-free (closed form fires), but its placements write
    symmetric soft-affinity weight (WSYM) that a LATER run's scores read —
    the float accumulation chain must replay the scan's rounding exactly."""
    nodes = [Node(name=f"n{i}",
                  allocatable=Resources.make(cpu="8", memory="16Gi",
                                             pods=110))
             for i in range(5)]
    web_sel = LabelSelector.of(match_labels={"app": "web"})
    # existing web pods seed the attraction targets
    existing = [Pod(name=f"w{i}", labels={"app": "web"},
                    requests=Resources.make(cpu="100m", memory="64Mi"),
                    node_name=f"n{i % 2}", creation_index=i)
                for i in range(2)]
    from kubernetes_tpu.api.types import WeightedPodAffinityTerm

    puller = Pod(
        name="t", labels={"app": "cache"},
        requests=Resources.make(cpu="100m", memory="64Mi"),
        affinity=Affinity(pod_preferred=(
            WeightedPodAffinityTerm(
                weight=37,
                term=PodAffinityTerm(selector=web_sel,
                                     topology_key=HOSTNAME)),)))
    web = Pod(name="t2", labels={"app": "web"},
              requests=Resources.make(cpu="150m", memory="96Mi"))
    pods = [_replica(puller, i) for i in range(6)] \
        + [dataclasses.replace(web, name=f"q{i}", creation_index=10 + i)
           for i in range(4)]
    for n in nodes:
        n.labels[HOSTNAME] = n.name
    _assert_engines_agree(nodes, existing, pods, check_state=False)


def test_adversarial_rw_volume_replicas_cap_one_per_node():
    """Replicas sharing a read-write volume conflict with themselves on a
    node (NoDiskConflict) — one per node, overflow fails, scan-equal."""
    from kubernetes_tpu.api.types import VolumeRef

    nodes = [Node(name=f"n{i}",
                  allocatable=Resources.make(cpu="8", memory="16Gi",
                                             pods=110))
             for i in range(3)]
    t = Pod(name="t", requests=Resources.make(cpu="100m", memory="64Mi"),
            volumes=(VolumeRef(vol_id="shared", driver="pd",
                               read_only=False),))
    pods = [_replica(t, i) for i in range(5)]
    s, _ = _assert_engines_agree(nodes, [], pods)
    node = np.asarray(s.node)[:5]
    placed = node[node >= 0]
    assert len(placed) == 3 and len(set(placed.tolist())) == 3


# --------------------------------------------------------------------- #
# gang / preemption / mesh paths
# --------------------------------------------------------------------- #


def test_gang_batches_bit_equal(monkeypatch):
    """The collapsed engine inside assign_gang's rejection loop: gang
    workloads (including statically-infeasible monster groups that force
    rejection rounds) place identically under both engines."""
    from kubernetes_tpu.models.workloads import gang_workload_pods, make_nodes
    from kubernetes_tpu.sched.cycle import BatchScheduler

    nodes = make_nodes(12, zones=3, racks_per_zone=2, cpu="16",
                       memory="64Gi")
    pods = gang_workload_pods(120)

    def run(engine):
        monkeypatch.setenv("KTPU_ASSIGN", engine)
        return BatchScheduler().schedule(nodes, [], pods).assignments

    a_scan = run("scan")
    a_runs = run("runs")
    assert a_scan == a_runs
    assert sum(1 for x in a_scan if x is not None) > 0


def test_preemption_drill_bit_equal(monkeypatch):
    """Preemption-triggering scheduler drill under both engines: same
    binds, same victims (the burst runs off the same snapshots either way,
    and the wave placements feeding it must be identical)."""
    from kubernetes_tpu.sched.preemption import Preemptor
    from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

    def drill(engine):
        monkeypatch.setenv("KTPU_ASSIGN", engine)
        clock = {"t": 0.0}
        preemptor = Preemptor()
        s = Scheduler(binder=RecordingBinder(), clock=lambda: clock["t"],
                      preemptor=preemptor)
        for i in range(2):
            s.on_node_add(Node(
                name=f"n{i}", labels={HOSTNAME: f"n{i}"},
                allocatable=Resources.make(cpu="2", memory="4Gi", pods=10)))
        # fill both nodes with low-priority pods
        for i in range(4):
            s.on_pod_add(Pod(
                name=f"f{i}", node_name=f"n{i % 2}",
                requests=Resources.make(cpu="900m", memory="1800Mi"),
                priority=0, creation_index=i))
        # high-priority replicas that need the space back
        for i in range(3):
            s.on_pod_add(Pod(
                name=f"vip{i}", priority=1000,
                requests=Resources.make(cpu="1500m", memory="3Gi"),
                creation_index=10 + i))
        for _ in range(4):
            s.schedule_pending()
            clock["t"] += 10.0
        return sorted(s.binder.bound), sorted(preemptor.evictor.evicted)

    assert drill("scan") == drill("runs")


@pytest.mark.mesh
@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 (virtual) devices — set XLA_FLAGS="
                           "--xla_force_host_platform_device_count=8")
def test_mesh_sharded_runs_bit_equal():
    """The collapsed engine under GSPMD sharding (node axis split over the
    8-way virtual mesh) must match BOTH its own unsharded run and the
    unsharded scan."""
    from kubernetes_tpu.models.workloads import flagship_pods, make_nodes
    from kubernetes_tpu.parallel.mesh import make_mesh, replicate, \
        shard_tables

    nodes = make_nodes(64, zones=8, racks_per_zone=4)
    pods = flagship_pods(96, groups=8)
    tables, ex, pe, uk, ev, d = _encode(nodes, [], pods)
    rc = _rc_of(pe)

    ref_scan = _run_impl("scan", tables, ex, pe, uk, ev, d.D, 0)
    ref_runs = _run_impl("runs", tables, ex, pe, uk, ev, d.D, rc)
    mesh = make_mesh(8)
    st = shard_tables(tables, mesh)
    sp = replicate(pe, mesh)
    se = replicate(ex, mesh)
    got = _run_impl("runs", st, se, sp, uk, ev, d.D, rc)

    np.testing.assert_array_equal(np.asarray(ref_runs.node),
                                  np.asarray(ref_scan.node))
    np.testing.assert_array_equal(np.asarray(got.node),
                                  np.asarray(ref_scan.node))
    assert int(np.asarray(got.feasible).sum()) > 0


# --------------------------------------------------------------------- #
# units: plan + classifier
# --------------------------------------------------------------------- #


def test_plan_runs_counts_and_bound():
    cls = np.array([0, 0, 0, 1, 1, 2, 0, 0], np.int32)
    pri = np.zeros(8, np.int32)
    cre = np.arange(8, dtype=np.int32)
    valid = np.ones(8, bool)
    nnr = np.full(8, -1, np.int32)
    plan = plan_runs(cls, pri, cre, valid, nnr)
    # runs: 0(×3), 1(×2), 2(×1), 0(×2) — class adjacency in CREATION order
    assert plan.n_runs == 4 and plan.n_valid == 8
    assert plan.rc >= plan.n_runs
    assert plan.collapse_ratio == pytest.approx(2.0)
    # invalid pods drop out of runs entirely
    valid[5] = False
    plan2 = plan_runs(cls, pri, cre, valid, nnr)
    assert plan2.n_valid == 7 and plan2.n_runs == 3  # runs 0,1 then 0 merge? no:
    # with pod 5 (class 2) invalid, the remaining order is 0,0,0,1,1,0,0 →
    # runs 0/1/0 = 3


@pytest.mark.parametrize("configured, has_node_name, engine", [
    ("waves", False, "waves"),
    ("waves", True, "scan"),     # spec.nodeName is per pod: the literal scan
    ("runs", False, "runs"),
    ("runs", True, "runs"),      # runs splits on the pin and keeps the batch
    ("scan", False, "scan"),
    ("scan", True, "scan"),
    ("wavs", False, "waves"),    # a typo lands on a known engine ...
    ("wavs", True, "scan"),      # ... and is then routed like it
    (None, False, "waves"),      # unset
])
def test_plan_engine_table(monkeypatch, configured, has_node_name, engine):
    """`plan_engine` is the one place a wave's program is chosen: the
    configured engine, the nodeName reroute, and `rc` from the RunPlan
    only where the engine is `runs` — and the cache emits a RunPlan
    exactly when the plan says `runs`."""
    from kubernetes_tpu.ops import configured_engine
    from kubernetes_tpu.ops.runs import RunPlan
    from kubernetes_tpu.sched.cycle import plan_engine
    from kubernetes_tpu.state.cache import SchedulerCache

    if configured is None:
        monkeypatch.delenv("KTPU_ASSIGN", raising=False)
    else:
        monkeypatch.setenv("KTPU_ASSIGN", configured)
    known = configured if configured in ("waves", "runs", "scan") \
        else "waves"
    assert configured_engine() == known
    plan = RunPlan(rc=64, n_runs=3, n_valid=40)
    assert plan_engine(has_node_name) == (engine, 0)
    assert plan_engine(has_node_name, plan) == \
        (engine, 64 if engine == "runs" else 0)

    cache = SchedulerCache()
    cache.add_node(Node(name="n0", labels={HOSTNAME: "n0"},
                        allocatable=Resources.make(cpu="4", memory="8Gi",
                                                   pods=10)))
    pods = [Pod(name=f"p{i}", creation_index=i,
                node_name="n0" if has_node_name and i == 1 else "",
                requests=Resources.make(cpu="100m", memory="64Mi"))
            for i in range(3)]
    snap = cache.snapshot(Encoder(), pods, None,
                          extra_intern=(UNSCHEDULABLE_TAINT_KEY,))
    assert snap.dims.has_node_name == has_node_name
    assert (snap.runs is not None) == (engine == "runs")
    assert plan_engine(snap.dims.has_node_name, snap.runs) == \
        (engine, snap.runs.rc if engine == "runs" else 0)


def test_plan_runs_extreme_negative_priority_matches_device_order():
    """INT32_MIN priorities wrap identically host- and device-side (the
    scan's own queue_order semantics) — the host bound must not undercount
    by ordering such pods differently."""
    cls = np.array([0, 1, 0, 1], np.int32)
    pri = np.array([-(2**31), 0, -(2**31), 0], np.int32)
    cre = np.arange(4, dtype=np.int32)
    plan = plan_runs(cls, pri, cre, np.ones(4, bool),
                     np.full(4, -1, np.int32))
    assert plan.n_runs >= 2  # never merges across the wrap boundary


def test_self_interaction_vector_classifies():
    """Plain replicas → closed form; self-anti-affine replicas → fallback;
    preferences toward ANOTHER class stay closed-form eligible."""
    nodes = [Node(name=f"n{i}", labels={HOSTNAME: f"n{i}"},
                  allocatable=Resources.make(cpu="8", memory="16Gi",
                                             pods=110))
             for i in range(3)]
    sel = LabelSelector.of(match_labels={"app": "db"})
    plain = Pod(name="a", labels={"app": "web"},
                requests=Resources.make(cpu="100m", memory="64Mi"),
                creation_index=0)
    selfanti = Pod(name="b", labels={"app": "db"},
                   requests=Resources.make(cpu="100m", memory="64Mi"),
                   affinity=Affinity(anti_required=(
                       PodAffinityTerm(selector=sel,
                                       topology_key=HOSTNAME),)),
                   creation_index=1)
    other = Pod(name="c", labels={"app": "cache"},
                requests=Resources.make(cpu="120m", memory="64Mi"),
                affinity=Affinity(anti_required=(
                    PodAffinityTerm(selector=sel,
                                    topology_key=HOSTNAME),)),
                creation_index=2)
    tables, ex, pe, uk, ev, d = _encode(nodes, [], [plain, selfanti, other])

    @jax.jit
    def classify(tables, ex):
        cyc = build_cycle(tables, ex, uk, ev, d.D)
        return self_interaction_vector(tables, cyc)

    selfi = np.asarray(classify(jax.device_put(tables), jax.device_put(ex)))
    cls = np.asarray(pe.cls)[:3]
    assert not selfi[cls[0]], "plain class must be closed-form eligible"
    assert selfi[cls[1]], "self-anti-affine class must take the fallback"
    assert not selfi[cls[2]], \
        "anti-affinity toward ANOTHER class is not self-interaction"
