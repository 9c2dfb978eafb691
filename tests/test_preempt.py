"""Preemption tests following the shapes of core/generic_scheduler_test.go
(TestSelectNodesForPreemption / TestPickOneNodeForPreemption) and
test/integration/scheduler/preemption_test.go."""

from kubernetes_tpu.api.types import (
    Affinity,
    LabelSelector,
    Node,
    Pod,
    PodAffinityTerm,
    Resources,
)
from kubernetes_tpu.sched.preemption import Preemptor
from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

HOSTNAME = "kubernetes.io/hostname"


class FakeClock:
    t = 0.0

    def __call__(self):
        return self.t


def mknode(name, cpu=2, mem="4Gi"):
    return Node(name=name, labels={HOSTNAME: name},
                allocatable=Resources.make(cpu=cpu, memory=mem, pods=110))


def bound(name, node, cpu="500m", mem="256Mi", priority=0, **kw):
    p = Pod(name=name, requests=Resources.make(cpu=cpu, memory=mem),
            priority=priority, **kw)
    p.node_name = node
    return p


def mksched(clock=None):
    clock = clock or FakeClock()
    s = Scheduler(binder=RecordingBinder(), clock=clock, preemptor=Preemptor())
    return s, clock


def test_preempts_lower_priority_and_schedules_after_eviction():
    from kubernetes_tpu.sched.metrics import (PREEMPTION_ATTEMPTS,
                                              PREEMPTION_VICTIMS)

    s, clock = mksched()
    s.on_node_add(mknode("n0", cpu=1))
    s.on_pod_add(bound("victim", "n0", cpu="800m", priority=0))
    s.on_pod_add(Pod(name="vip", priority=100,
                     requests=Resources.make(cpu="800m", memory="256Mi")))
    attempts, victims = PREEMPTION_ATTEMPTS.value(), PREEMPTION_VICTIMS.value()
    st = s.schedule_pending()
    assert st.scheduled == 0
    # preemption ran: victim evicted, vip nominated on n0, requeued
    assert s.preemptor.evictor.evicted == ["default/victim"]
    # and the two series the catalogue lists are fed (declared, never
    # incremented, until ISSUE 24)
    assert PREEMPTION_ATTEMPTS.value() == attempts + 1
    assert PREEMPTION_VICTIMS.value() == victims + 1
    assert s.queue.nominated_node("default/vip") == "n0"
    assert s.cache.get_pod("default/victim") is None
    clock.t = 5.0
    st2 = s.schedule_pending()
    assert st2.assignments.get("default/vip") == "n0"
    # nomination cleared once bound
    assert s.queue.nominated_node("default/vip") is None


def test_replica_burst_preempts_through_the_wave_engine():
    """Two full nodes, three high-priority replicas that each need a node's
    worth back: the binds and the victims of the drill on the engine that
    serves (the wave placements feed the burst's what-if)."""
    s, clock = mksched()
    for i in range(2):
        s.on_node_add(Node(
            name=f"n{i}", labels={HOSTNAME: f"n{i}"},
            allocatable=Resources.make(cpu="2", memory="4Gi", pods=10)))
    for i in range(4):
        s.on_pod_add(bound(f"f{i}", f"n{i % 2}", cpu="900m", mem="1800Mi",
                           creation_index=i))
    for i in range(3):
        s.on_pod_add(Pod(
            name=f"vip{i}", priority=1000, creation_index=10 + i,
            requests=Resources.make(cpu="1500m", memory="3Gi")))
    for _ in range(4):
        s.schedule_pending()
        clock.t += 10.0
    assert sorted(s.binder.bound) == [("default/vip0", "n0"),
                                      ("default/vip1", "n1")]
    assert sorted(s.preemptor.evictor.evicted) == [
        f"default/f{i}" for i in range(4)]


def test_no_preemption_of_equal_or_higher_priority():
    s, clock = mksched()
    s.on_node_add(mknode("n0", cpu=1))
    s.on_pod_add(bound("peer", "n0", cpu="800m", priority=100))
    s.on_pod_add(Pod(name="vip", priority=100,
                     requests=Resources.make(cpu="800m", memory="256Mi")))
    st = s.schedule_pending()
    assert st.unschedulable == 1
    assert s.preemptor.evictor.evicted == []
    assert s.cache.get_pod("default/peer") is not None


def test_zero_priority_pod_never_preempts():
    s, clock = mksched()
    s.on_node_add(mknode("n0", cpu=1))
    s.on_pod_add(bound("victim", "n0", cpu="800m", priority=-5))
    s.on_pod_add(Pod(name="plain", priority=0,
                     requests=Resources.make(cpu="800m", memory="256Mi")))
    st = s.schedule_pending()
    assert st.unschedulable == 1
    assert s.preemptor.evictor.evicted == []


def test_minimal_victim_set_reprieve():
    """Node has three low-priority pods but evicting ONE 600m pod suffices for
    the 500m preemptor: reprieve must restore the others (selectVictimsOnNode
    pass 2)."""
    s, clock = mksched()
    s.on_node_add(mknode("n0", cpu=2))
    s.on_pod_add(bound("a", "n0", cpu="600m", priority=1))
    s.on_pod_add(bound("b", "n0", cpu="600m", priority=2))
    s.on_pod_add(bound("c", "n0", cpu="600m", priority=3))
    s.on_pod_add(Pod(name="vip", priority=100,
                     requests=Resources.make(cpu="500m", memory="128Mi")))
    s.schedule_pending()
    # greedy reprieve in priority-desc order keeps c and b (2*600+500 ≤ 2000),
    # evicts only the lowest-priority a
    assert s.preemptor.evictor.evicted == ["default/a"]


def test_picks_node_with_lowest_max_victim_priority():
    """pickOneNodeForPreemption criterion 2: prefer the node whose highest
    victim priority is smallest."""
    s, clock = mksched()
    s.on_node_add(mknode("n0", cpu=1))
    s.on_node_add(mknode("n1", cpu=1))
    s.on_pod_add(bound("hi", "n0", cpu="900m", priority=50))
    s.on_pod_add(bound("lo", "n1", cpu="900m", priority=5))
    s.on_pod_add(Pod(name="vip", priority=100,
                     requests=Resources.make(cpu="500m", memory="128Mi")))
    s.schedule_pending()
    assert s.preemptor.evictor.evicted == ["default/lo"]
    assert s.queue.nominated_node("default/vip") == "n1"


def test_preemption_helps_anti_affinity_block():
    """Victim's anti-affinity blocks the preemptor; eviction clears it — and
    the reprieve pass must NOT restore the blocking victim."""
    sel = LabelSelector.of(match_labels={"app": "red"})
    s, clock = mksched()
    s.on_node_add(mknode("n0"))
    blocker = bound("blocker", "n0", cpu="100m", priority=1)
    blocker.labels = {"app": "blue"}
    blocker.affinity = Affinity(anti_required=(
        PodAffinityTerm(selector=sel, topology_key=HOSTNAME),))
    s.on_pod_add(blocker)
    vip = Pod(name="vip", priority=100, labels={"app": "red"},
              requests=Resources.make(cpu="100m", memory="64Mi"))
    s.on_pod_add(vip)
    st = s.schedule_pending()
    assert st.scheduled == 0
    assert s.preemptor.evictor.evicted == ["default/blocker"]
    clock.t = 5.0
    st2 = s.schedule_pending()
    assert st2.assignments.get("default/vip") == "n0"


def test_no_candidate_when_pod_cannot_fit_even_empty():
    s, clock = mksched()
    s.on_node_add(mknode("n0", cpu=1))
    s.on_pod_add(bound("v", "n0", cpu="500m", priority=0))
    s.on_pod_add(Pod(name="huge", priority=100,
                     requests=Resources.make(cpu=8, memory="256Mi")))
    st = s.schedule_pending()
    assert st.unschedulable == 1
    assert s.preemptor.evictor.evicted == []


# --------------------------------------------------------------------------- #
# PDB-aware preemption (pickOneNodeForPreemption criterion 1 + the
# violating-victims-first reprieve, generic_scheduler.go:903-928,1149-1156)
# --------------------------------------------------------------------------- #


def mksched_pdb(pdbs, clock=None):
    clock = clock or FakeClock()
    s = Scheduler(binder=RecordingBinder(), clock=clock,
                  preemptor=Preemptor(pdb_source=lambda: pdbs))
    return s, clock


def test_pdb_protected_node_avoided():
    """Criterion 1: with equal victims otherwise, the node whose victim's
    eviction would violate a PDB loses to the unprotected node."""
    sel = LabelSelector.of(match_labels={"app": "guarded"})
    s, clock = mksched_pdb([("default", sel, 0)])
    s.on_node_add(mknode("n0", cpu=1))
    s.on_node_add(mknode("n1", cpu=1))
    guarded = bound("guarded", "n0", cpu="900m", priority=5)
    guarded.labels = {"app": "guarded"}
    s.on_pod_add(guarded)
    s.on_pod_add(bound("plain", "n1", cpu="900m", priority=5))
    s.on_pod_add(Pod(name="vip", priority=100,
                     requests=Resources.make(cpu="500m", memory="128Mi")))
    s.schedule_pending()
    assert s.preemptor.evictor.evicted == ["default/plain"]
    assert s.queue.nominated_node("default/vip") == "n1"
    assert s.preemptor.last_pdb_violations == 0


def test_pdb_with_budget_left_does_not_block():
    """disruptionsAllowed > 0 ⇒ eviction is not a violation."""
    sel = LabelSelector.of(match_labels={"app": "guarded"})
    s, clock = mksched_pdb([("default", sel, 2)])
    s.on_node_add(mknode("n0", cpu=1))
    guarded = bound("guarded", "n0", cpu="900m", priority=5)
    guarded.labels = {"app": "guarded"}
    s.on_pod_add(guarded)
    s.on_pod_add(Pod(name="vip", priority=100,
                     requests=Resources.make(cpu="500m", memory="128Mi")))
    s.schedule_pending()
    assert s.preemptor.evictor.evicted == ["default/guarded"]


def test_pdb_violating_victim_reprieved_first():
    """Two potential victims; evicting either frees enough. The PDB-protected
    one must be reprieved (restored first) and the plain one evicted."""
    sel = LabelSelector.of(match_labels={"app": "guarded"})
    s, clock = mksched_pdb([("default", sel, 0)])
    s.on_node_add(mknode("n0", cpu=2))
    guarded = bound("guarded", "n0", cpu="900m", priority=5)
    guarded.labels = {"app": "guarded"}
    s.on_pod_add(guarded)
    s.on_pod_add(bound("plain", "n0", cpu="900m", priority=5))
    s.on_pod_add(Pod(name="vip", priority=100,
                     requests=Resources.make(cpu="1", memory="128Mi")))
    s.schedule_pending()
    assert s.preemptor.evictor.evicted == ["default/plain"]
    assert s.preemptor.last_pdb_violations == 0


def test_unavoidable_pdb_violation_is_counted():
    sel = LabelSelector.of(match_labels={"app": "guarded"})
    s, clock = mksched_pdb([("default", sel, 0)])
    s.on_node_add(mknode("n0", cpu=1))
    guarded = bound("guarded", "n0", cpu="900m", priority=5)
    guarded.labels = {"app": "guarded"}
    s.on_pod_add(guarded)
    s.on_pod_add(Pod(name="vip", priority=100,
                     requests=Resources.make(cpu="500m", memory="128Mi")))
    s.schedule_pending()
    assert s.preemptor.evictor.evicted == ["default/guarded"]
    assert s.preemptor.last_pdb_violations == 1


def test_latest_start_time_tiebreak():
    """Criterion 5: all else equal, prefer the node whose highest-priority
    victim started LATEST (creation_index proxy)."""
    s, clock = mksched()
    s.on_node_add(mknode("n0", cpu=1))
    s.on_node_add(mknode("n1", cpu=1))
    old = bound("old", "n0", cpu="900m", priority=5)
    old.creation_index = 1
    young = bound("young", "n1", cpu="900m", priority=5)
    young.creation_index = 99
    s.on_pod_add(old)
    s.on_pod_add(young)
    s.on_pod_add(Pod(name="vip", priority=100,
                     requests=Resources.make(cpu="500m", memory="128Mi")))
    s.schedule_pending()
    assert s.preemptor.evictor.evicted == ["default/young"]


def test_reprieve_conservatism_vs_oracle():
    """Quantified conservatism bound (docs/PARITY.md #4): the device reprieve
    never evicts FEWER victims than the reference's selectVictimsOnNode
    replay, and after evicting the device's victims the preemptor always
    fits — conservative, never unsound."""
    import random

    from kubernetes_tpu.api import semantics as sem

    def oracle_victims(pod, node, nodes, existing):
        nodes_by_name = {n.name: n for n in nodes}

        def fits(exist):
            used = Resources(
                milli_cpu=sum(e.requests.milli_cpu for e in exist
                              if e.node_name == node.name),
                memory_kib=sum(e.requests.memory_kib for e in exist
                               if e.node_name == node.name))
            cnt = sum(1 for e in exist if e.node_name == node.name)
            ok_res, _ = sem.pod_fits_resources(pod, node, used, cnt)
            return (ok_res
                    and sem.interpod_affinity_fits(pod, node, nodes_by_name,
                                                   exist)
                    and sem.topology_spread_fits(pod, node, nodes, exist))

        pot = [e for e in existing
               if e.node_name == node.name and e.priority < pod.priority]
        others = [e for e in existing if e not in pot]
        if not fits(others):
            return None
        kept, victims = [], []
        for v in sorted(pot, key=lambda e: (-e.priority, e.creation_index)):
            if fits(others + kept + [v]):
                kept.append(v)
            else:
                victims.append(v)
        return victims

    rng = random.Random(7)
    extra_evictions = 0
    total_evictions = 0
    for trial in range(6):
        s, clock = mksched()
        n_nodes = rng.randint(1, 3)
        nodes = [mknode(f"n{i}", cpu=2) for i in range(n_nodes)]
        for n in nodes:
            s.on_node_add(n)
        existing = []
        for i in range(rng.randint(1, 5)):
            v = bound(f"e{i}", f"n{rng.randrange(n_nodes)}",
                      cpu=rng.choice(["400m", "800m", "1200m"]),
                      priority=rng.randrange(3))
            v.labels = {"app": rng.choice(["red", "blue"])}
            if rng.random() < 0.4:
                v.affinity = Affinity(anti_required=(PodAffinityTerm(
                    selector=LabelSelector.of(
                        match_labels={"app": rng.choice(["red", "blue"])}),
                    topology_key=HOSTNAME),))
            v.creation_index = i
            existing.append(v)
            s.on_pod_add(v)
        vip = Pod(name="vip", priority=100, labels={"app": "red"},
                  requests=Resources.make(cpu="1500m", memory="128Mi"))
        s.on_pod_add(vip)
        s.schedule_pending()
        evicted = set(s.preemptor.evictor.evicted)
        if not evicted:
            continue
        node_name = s.queue.nominated_node("default/vip")
        node = next(n for n in nodes if n.name == node_name)
        want = oracle_victims(vip, node, nodes, existing)
        assert want is not None, "device chose a non-candidate node"
        want_keys = {v.key for v in want}
        assert want_keys <= evicted, (
            f"device under-evicted: oracle wants {want_keys}, got {evicted}")
        # soundness: the preemptor fits with the device's victims gone
        survivors = [e for e in existing if e.key not in evicted]
        by_name = {n.name: n for n in nodes}
        used = Resources(
            milli_cpu=sum(e.requests.milli_cpu for e in survivors
                          if e.node_name == node.name),
            memory_kib=sum(e.requests.memory_kib for e in survivors
                           if e.node_name == node.name))
        cntp = sum(1 for e in survivors if e.node_name == node.name)
        ok_res, _ = sem.pod_fits_resources(vip, node, used, cntp)
        assert ok_res
        assert sem.interpod_affinity_fits(vip, node, by_name, survivors)
        extra_evictions += len(evicted) - len(want_keys)
        total_evictions += len(evicted)
    # the conservatism is bounded: documented over-eviction only, and the
    # scan evicted SOMETHING across the trials
    assert total_evictions > 0
    assert extra_evictions <= total_evictions


def test_server_preemption_deletes_victim_through_api():
    """Round-5 regression (found by the scheduler-in-the-loop bench): the
    SchedulerServer's preemptor must evict THROUGH THE API. The cache-only
    evictor freed resources in the scheduler's head while the victim pod
    lived on in the apiserver — the preemptor pod then bound onto a node
    whose real occupant was never removed (double-booking)."""
    import time

    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.machinery import errors as merrors
    from kubernetes_tpu.sched.server import SchedulerServer

    api = APIServer()
    client = Client.local(api)
    caps = {"capacity": {"cpu": "4", "memory": "8Gi", "pods": "10"},
            "allocatable": {"cpu": "4", "memory": "8Gi", "pods": "10"}}
    client.nodes.create({"apiVersion": "v1", "kind": "Node",
                         "metadata": {"name": "only",
                                      "labels": {"pin": "y"}},
                         "status": caps})
    server = SchedulerServer(client, cycle_interval=0.02,
                             batch_window=0.02).start()
    try:
        client.pods.create({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "squatter", "namespace": "default"},
            "spec": {"nodeName": "only", "priority": 0,
                     "containers": [{"name": "c", "image": "i",
                                     "resources": {"requests": {
                                         "cpu": "3500m",
                                         "memory": "6Gi"}}}]}})
        time.sleep(0.5)
        client.pods.create({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "vip", "namespace": "default"},
            "spec": {"priority": 1000, "nodeSelector": {"pin": "y"},
                     "containers": [{"name": "c", "image": "i",
                                     "resources": {"requests": {
                                         "cpu": "3", "memory": "4Gi"}}}]}})
        deadline = time.time() + 60
        while time.time() < deadline:
            if client.pods.get("vip").get("spec", {}).get("nodeName"):
                break
            time.sleep(0.1)
        assert client.pods.get("vip")["spec"]["nodeName"] == "only"
        # the victim is REALLY gone from the API, not just the cache
        try:
            sq = client.pods.get("squatter")
            assert sq.get("metadata", {}).get("deletionTimestamp") or \
                sq.get("status", {}).get("phase") == "Failed", \
                f"squatter survived: {sq.get('status')}"
        except merrors.StatusError as e:
            assert merrors.is_not_found(e)
    finally:
        server.stop()
        api.close()


# --------------------------------------------------------------------------- #
# ISSUE 43: the what-if's hard-spread row reads the lane's own eligible-masked
# in-domain counts through `ops/topospread.py spread_counts` (a product
# against the cycle's same-domain matrices, or one scatter-add and gather)
# --------------------------------------------------------------------------- #

import pytest  # noqa: E402

ZONE = "topology.kubernetes.io/zone"


def _spread_whatif_cluster(case):
    """9 nodes in three zones (one node without the zone label), each nearly
    full of low-priority `web` pods, unevenly by zone, and four `web` pods
    no preemptor may evict on two nodes of one zone; two preemptors of
    priority 10 that must evict to land, under hard spread over `case`'s
    key, the second behind a node selector."""
    from kubernetes_tpu.api.types import (TopologySpreadConstraint,
                                          UnsatisfiableAction)

    nodes = []
    for i in range(9):
        labels = {HOSTNAME: f"n{i}", "pool": "ab"[i % 2]}
        if i != 4:
            labels[ZONE] = f"z{i % 3}"
        nodes.append(Node(name=f"n{i}", labels=labels,
                          allocatable=Resources.make(cpu=4, memory="8Gi",
                                                     pods=110)))
    existing = []
    for i in range(9):
        for j in range(2 + (i % 3 == 0) + (i == 1)):
            existing.append(bound(f"low-{i}-{j}", f"n{i}", cpu="1",
                                  priority=j % 2, labels={"app": "web"}))
    # survivors of the what-if (priority over the preemptors'): the counts
    # the spread row reads once every victim is gone
    for i in (0, 0, 3, 3):
        existing.append(bound(f"keep-{len(existing)}", f"n{i}", cpu="100m",
                              priority=20, labels={"app": "web"}))
    key = HOSTNAME if case == "hostname-spread" else ZONE
    spread = (TopologySpreadConstraint(
        max_skew=1, topology_key=key,
        when_unsatisfiable=UnsatisfiableAction.DO_NOT_SCHEDULE,
        selector=LabelSelector.of(match_labels={"app": "web"})),)
    pending = [
        Pod(name="hi-0", labels={"app": "web"}, priority=10,
            requests=Resources.make(cpu="3", memory="1Gi"),
            topology_spread=spread, creation_index=0),
        Pod(name="hi-1", labels={"app": "web"}, priority=10,
            requests=Resources.make(cpu="3", memory="1Gi"),
            node_selector={"pool": "a"}, topology_spread=spread,
            creation_index=1),
    ]
    return nodes, existing, pending


@pytest.mark.parametrize("case", ["zone-spread", "hostname-spread"])
def test_whatif_with_product_equals_scatter_and_the_parents_row(
        case, monkeypatch):
    """Every field of the what-if's result for two lanes (the chosen node,
    the candidates' count, the whole order, the victims) with the cycle's
    same-domain matrices, without them, and with the PARENT's spread row
    (its own scatter-add + gather and ELD's scatter-max) put back in its
    place: identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import spread_parent_forms as parent
    from kubernetes_tpu.ops import preempt as preempt_mod
    from kubernetes_tpu.ops.lattice import build_cycle
    from kubernetes_tpu.sched.cycle import UNSCHEDULABLE_TAINT_KEY
    from kubernetes_tpu.state.encode import Encoder

    nodes, existing, pending = _spread_whatif_cluster(case)
    enc = Encoder()
    enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
    enc.vocabs.label_vals.intern("")
    tables, ex, pe, d = enc.encode_cluster(nodes, existing, pending, None)
    uk = jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY))
    ev = jnp.int32(enc.vocabs.label_vals.get(""))
    assert d.domain_sum("waves") == "product"
    lanes = (pe.cls[:2], pe.node_name_req[:2], pe.priority[:2])

    def whatif(scatter):
        def f(tables, ex):
            cyc = build_cycle(tables, ex, uk, ev, d.D)
            assert cyc.SAME is not None
            if scatter:
                cyc = cyc._replace(SAME=None)
            return preempt_mod.preempt_batch(tables, cyc, ex, *lanes, d.D)

        return jax.tree.map(np.asarray, jax.jit(f)(tables, ex))

    product, scatter = whatif(False), whatif(True)

    def parents_row(cls, classes, terms, TM, CNT, _ELN, nm_row, nodes, D,
                    _same=None):
        eld = parent.eligible_domains(
            parents_row.node_match, classes, nodes, D)
        return parent.spread_row(cls, classes, terms, TM, CNT, eld, nm_row,
                                 nodes, D)

    def with_parents_row(tables, ex):
        cyc = build_cycle(tables, ex, uk, ev, d.D)
        parents_row.node_match = cyc.static.node_match
        return preempt_mod.preempt_batch(tables, cyc, ex, *lanes, d.D)

    monkeypatch.setattr(preempt_mod, "spread_row", parents_row)
    old = jax.tree.map(np.asarray, jax.jit(with_parents_row)(tables, ex))
    for name, p, s, o in zip(product._fields, product, scatter, old):
        np.testing.assert_array_equal(p, s, name)
        np.testing.assert_array_equal(p, o, name)
    n_valid = int(np.asarray(tables.nodes.valid).sum())
    # both lanes find a node, and hard spread refuses some node to each
    assert (product.node >= 0).all() and product.victims.any()
    assert (product.n_candidates > 0).all()
    assert (product.n_candidates < n_valid).all()
    assert not product.bulk.any()
