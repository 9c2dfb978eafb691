"""Host-pipeline overlap PR tests: vectorized ingest equivalence, the fused
preemption burst vs the reference oracle, prewarmed-executable reuse, and the
security/machinery hardening satellites that ride along."""

import random

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
    Toleration,
    TopologySpreadConstraint,
    UnsatisfiableAction,
    VolumeRef,
)
from kubernetes_tpu.state.encode import Encoder

HOSTNAME = "kubernetes.io/hostname"


def random_pod(rng: random.Random, i: int) -> Pod:
    """A randomized event-stream pod: templates with noise, labels both
    referenced and unreferenced, occasional selectors/tolerations/ports —
    the shapes the fingerprint memo must not confuse."""
    tier = rng.randrange(4)
    p = Pod(
        name=f"p-{i}",
        namespace=rng.choice(["default", "batch", "prod"]),
        labels={"app": f"app-{rng.randrange(6)}",
                "job-id": f"j{i}"},   # high-cardinality, never referenced
        requests=Resources.make(cpu=["100m", "250m", "500m", "1"][tier],
                                memory=["128Mi", "512Mi", "1Gi", "2Gi"][tier]),
        priority=rng.randrange(3),
        creation_index=i,
    )
    if rng.random() < 0.3:
        p.node_selector = {"pool": rng.choice(["a", "b"])}
    if rng.random() < 0.25:
        p.affinity = Affinity(anti_required=(PodAffinityTerm(
            selector=LabelSelector.of(
                match_labels={"app": f"app-{rng.randrange(6)}"}),
            topology_key=HOSTNAME),))
    if rng.random() < 0.2:
        p.tolerations = (Toleration(key="dedicated",
                                    value=rng.choice(["gpu", "tpu"])),)
    if rng.random() < 0.15:
        p.host_ports = (HostPort(port=8000 + rng.randrange(4)),)
    if rng.random() < 0.2:
        p.pod_group = f"g{rng.randrange(8)}"
        p.min_member = 2
    # cover EVERY class_id field so a fingerprint (or its inlined copy in
    # intern_pods) that drops a spec component fails this test, not prod
    if rng.random() < 0.2:
        p.topology_spread = (TopologySpreadConstraint(
            max_skew=1 + rng.randrange(2), topology_key="zone",
            when_unsatisfiable=UnsatisfiableAction.SCHEDULE_ANYWAY,
            selector=LabelSelector.of(
                match_labels={"app": f"app-{rng.randrange(6)}"})),)
    if rng.random() < 0.2:
        p.spread_selectors = (LabelSelector.of(
            match_labels={"app": f"app-{rng.randrange(6)}"}),)
    if rng.random() < 0.2:
        p.images = (f"img-{rng.randrange(5)}:latest",)
    if rng.random() < 0.2:
        p.limits = Resources.make(cpu="2", memory="4Gi")
    if rng.random() < 0.15:
        p.volumes = (VolumeRef(driver="pd", vol_id=f"v{rng.randrange(6)}",
                               read_only=bool(rng.randrange(2))),)
    return p


def reference_walk(enc: Encoder, pods) -> list:
    """The pre-vectorization per-object walk: full class_id spec walk for
    EVERY pod (the fingerprint memo is cleared after each row so it can
    never short-circuit), with the caller-side projection re-walk loop."""
    rows = []
    for _walk_pass in range(8):
        rows = []
        for p in pods:
            enc._pod_rows.pop(id(p), None)   # force a fresh walk
            row = enc.pod_row(p)
            enc._class_memo.clear()
            rows.append(row)
        if not enc.classes_stale:
            break
        enc.projection_rewalk()
    assert not enc.classes_stale
    return rows


class TestIngestEquivalence:
    def test_batch_intern_matches_per_object_walk(self):
        """intern_pods (columnar batch path) and the memo-free per-object
        class walk produce identical rows, identical class registries, and
        identical device arrays on randomized event streams."""
        rng = random.Random(42)
        pods = [random_pod(rng, i) for i in range(600)]

        enc_fast, enc_slow = Encoder(), Encoder()
        for _walk_pass in range(8):
            enc_fast.intern_pods(pods)
            if not enc_fast.classes_stale:
                break
            enc_fast.projection_rewalk()
        rows_fast = [enc_fast._pod_rows[id(p)][1] for p in pods]
        rows_slow = reference_walk(enc_slow, pods)

        assert rows_fast == rows_slow
        assert len(enc_fast.class_reg) == len(enc_slow.class_reg)
        assert enc_fast._class_spec == enc_slow._class_spec
        assert len(enc_fast.pod_groups) == len(enc_slow.pod_groups)
        assert enc_fast.group_min == enc_slow.group_min

        d = enc_fast.dims(4, 1, len(pods), [])
        pe_fast = enc_fast.build_pod_arrays(pods, d, capacity=d.P)
        pe_slow = enc_slow.build_pod_arrays(pods, d, capacity=d.P)
        for a, b in zip(pe_fast, pe_slow):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_projection_widening_invalidates_fingerprint_memo(self):
        """Two pods differing only in an initially-unreferenced label key
        share a class until a selector references the key — then the re-walk
        must split them (batch path included)."""
        a = Pod(name="a", labels={"tier": "gold"},
                requests=Resources.make(cpu="1"))
        b = Pod(name="b", labels={"tier": "bronze"},
                requests=Resources.make(cpu="1"))
        enc = Encoder()
        enc.intern_pods([a, b])
        assert enc._pod_rows[id(a)][1][2] == enc._pod_rows[id(b)][1][2]

        watcher = Pod(name="w", requests=Resources.make(cpu="1"),
                      affinity=Affinity(pod_required=(PodAffinityTerm(
                          selector=LabelSelector.of(
                              match_labels={"tier": "gold"}),
                          topology_key=HOSTNAME),)))
        pods = [a, b, watcher]
        for _walk_pass in range(8):
            enc.intern_pods(pods)
            if not enc.classes_stale:
                break
            enc.projection_rewalk()
        assert enc._pod_rows[id(a)][1][2] != enc._pod_rows[id(b)][1][2]

    def test_unconverged_projection_raises(self):
        """The 8-pass projection loop failing to converge is a loud error,
        not a silently stale snapshot (state/cache.py + encode.py)."""
        from kubernetes_tpu.state.cache import SchedulerCache
        from kubernetes_tpu.state.encode import ProjectionUnconvergedError

        enc = Encoder()
        cache = SchedulerCache()
        cache.add_node(Node(name="n0",
                            allocatable=Resources.make(cpu="8",
                                                       memory="16Gi",
                                                       pods=110)))
        enc.classes_stale = True   # simulate a walk that never settles
        orig = enc.projection_rewalk
        enc.projection_rewalk = lambda: None   # stale bit never clears
        try:
            with pytest.raises(ProjectionUnconvergedError):
                cache.snapshot(enc, [Pod(name="p",
                                         requests=Resources.make(cpu="1"))])
        finally:
            enc.projection_rewalk = orig


def mknode(name, cpu=2, mem="4Gi"):
    return Node(name=name, labels={HOSTNAME: name},
                allocatable=Resources.make(cpu=cpu, memory=mem, pods=110))


def bound(name, node, cpu="500m", mem="256Mi", priority=0, idx=0):
    p = Pod(name=name, requests=Resources.make(cpu=cpu, memory=mem),
            priority=priority, creation_index=idx)
    p.node_name = node
    return p


def _labelled(name, node, app, priority, cpu="200m", idx=0, **aff):
    p = bound(name, node, cpu=cpu, priority=priority, idx=idx)
    p.labels = {"app": app}
    if aff:
        p.affinity = Affinity(**aff)
    return p


def _term(app, key=HOSTNAME):
    return PodAffinityTerm(selector=LabelSelector.of(
        match_labels={"app": app}), topology_key=key)


def _what_if_case(case):
    """(nodes, existing, pending, PDB-blocked keys) of one pinned what-if."""
    if case == "burst-lanes":      # also test_burst_lanes_match_single_…
        rng = random.Random(3)
        nodes = [mknode(f"n{i}") for i in range(4)]
        existing = [bound(f"e{i}", f"n{rng.randrange(4)}",
                          cpu=rng.choice(["400m", "900m", "1500m"]),
                          priority=rng.randrange(4), idx=i)
                    for i in range(10)]
        pending = [Pod(name=f"vip{i}", priority=10 + i,
                       requests=Resources.make(cpu="1200m", memory="128Mi"),
                       creation_index=100 + i) for i in range(3)]
        return nodes, existing, pending, set()
    if case == "pdb":              # a trial of test_burst_vs_pick_one_node_…
        rng = random.Random(11)
        nodes = [mknode(f"n{i}", cpu=2) for i in range(4)]
        existing = [bound(f"e{i}", f"n{i % 4}",
                          cpu=rng.choice(["300m", "600m"]),
                          priority=rng.randrange(5), idx=i)
                    for i in range(12)]
        pdb = {e.key for e in existing if rng.random() < 0.4}
        pending = [Pod(name="vip", priority=50, creation_index=99,
                       requests=Resources.make(cpu="1500m",
                                               memory="128Mi"))]
        return nodes, existing, pending, pdb
    nodes = [mknode(f"n{i}", cpu=1) for i in range(3)]
    nodes[0].labels["zone"] = nodes[1].labels["zone"] = "a"
    nodes[2].labels["zone"] = "b"
    if case == "anti-affinity-holder":   # tests/test_preempt.py's blocker
        existing = [
            _labelled("blocker", "n0", "blue", 1, anti_required=(_term("red"),)),
            _labelled("peer", "n1", "blue", 200,
                      anti_required=(_term("red"),)),
            _labelled("fill", "n2", "grey", 1, cpu="900m")]
        pending = [Pod(name="vip", priority=100, labels={"app": "red"},
                       creation_index=50,
                       requests=Resources.make(cpu="300m", memory="64Mi"))]
        return nodes, existing, pending, set()
    if case == "two-priorities-one-burst":
        # lane 0 (priority 5) may evict only the priority-1 holder, lane 1
        # (priority 50) both holders: two survivor histograms in one burst
        existing = [
            _labelled("lo", "n0", "blue", 1, anti_required=(_term("red"),)),
            _labelled("hi", "n1", "blue", 10, anti_required=(_term("red"),)),
            _labelled("top", "n2", "blue", 99, anti_required=(_term("red"),)),
            _labelled("red0", "n1", "red", 2, cpu="100m", idx=1),
            _labelled("red1", "n2", "red", 20, cpu="100m", idx=2),
            _labelled("fill2", "n2", "grey", 1, cpu="500m", idx=3)]
        pending = [
            Pod(name="low", priority=5, labels={"app": "red"},
                creation_index=50,
                requests=Resources.make(cpu="300m", memory="64Mi")),
            Pod(name="high", priority=50, labels={"app": "red"},
                creation_index=51,
                requests=Resources.make(cpu="300m", memory="64Mi")),
            # required affinity: the match it needs must SURVIVE the what-if
            Pod(name="joiner", priority=15, labels={"app": "grey"},
                creation_index=52,
                requests=Resources.make(cpu="650m", memory="64Mi"),
                affinity=Affinity(pod_required=(_term("red"),)))]
        return nodes, existing, pending, {"default/lo"}
    if case == "hard-spread":
        spread = (TopologySpreadConstraint(
            max_skew=1, topology_key="zone",
            when_unsatisfiable=UnsatisfiableAction.DO_NOT_SCHEDULE,
            selector=LabelSelector.of(match_labels={"app": "web"})),)
        existing = [
            _labelled("w0", "n0", "web", 1, cpu="600m"),
            _labelled("w1", "n1", "web", 1, cpu="600m", idx=1),
            _labelled("w2", "n0", "web", 30, cpu="300m", idx=2),
            _labelled("w3", "n2", "web", 30, cpu="100m", idx=3),
            _labelled("g", "n2", "grey", 5, cpu="900m", idx=4)]
        pending = [Pod(name="w-new", priority=20, labels={"app": "web"},
                       creation_index=60, topology_spread=spread,
                       requests=Resources.make(cpu="500m", memory="64Mi"))]
        return nodes, existing, pending, set()
    raise KeyError(case)


_WHAT_IF_PINNED = {   # per lane: (node, victims, n_candidates, n_pdb_violations)
    "anti-affinity-holder": [("n0", ["default/blocker"], 2, 0)],
    "burst-lanes": [("n0", ["default/e2"], 4, 0)] * 3,
    "hard-spread": [("n1", ["default/w1"], 3, 0)],
    "pdb": [("n1", ["default/e1", "default/e5"], 4, 0)],
    "two-priorities-one-burst": [("n0", ["default/lo"], 1, 1),
                                 ("n1", ["default/hi"], 2, 0),
                                 ("n2", ["default/fill2"], 1, 0)],
}


class TestFusedPreemptionBurst:
    def _snapshot(self, nodes, existing, pending):
        from kubernetes_tpu.sched.cycle import snapshot_with_keys
        from kubernetes_tpu.state.cache import SchedulerCache

        cache = SchedulerCache()
        enc = Encoder()
        for n in nodes:
            cache.add_node(n)
        for e in existing:
            cache.add_pod(e)
        snap, keys = snapshot_with_keys(cache, enc, pending, None)
        return cache, enc, snap, keys

    def test_burst_lanes_match_single_lane_dispatch(self):
        """Each lane of the vmapped burst equals the single-pod what-if on
        the same snapshot — including the padded tail lanes."""
        import jax
        import jax.numpy as jnp

        from kubernetes_tpu.ops.lattice import (
            build_cycle, default_engine_config)
        from kubernetes_tpu.ops.preempt import preempt_batch, preempt_for_pod

        nodes, existing, pending, _pdb = _what_if_case("burst-lanes")
        _cache, _enc, snap, keys = self._snapshot(nodes, existing, pending)
        uk, ev = keys
        cyc = build_cycle(snap.tables, snap.existing, uk, ev, snap.dims.D,
                          jnp.float32(1.0), default_engine_config())
        B = snap.pending.cls.shape[0]
        cls_b = snap.pending.cls
        nnr_b = snap.pending.node_name_req
        prio_b = snap.pending.priority
        batch = preempt_batch(snap.tables, cyc, snap.existing,
                              cls_b, nnr_b, prio_b, snap.dims.D)
        for lane in range(len(pending)):
            single = preempt_for_pod(
                snap.tables, cyc, snap.existing, cls_b[lane], nnr_b[lane],
                prio_b[lane], snap.dims.D)
            assert int(batch.node[lane]) == int(single.node)
            assert np.array_equal(np.asarray(jax.device_get(
                batch.victims[lane])), np.asarray(jax.device_get(
                    single.victims)))

    def test_burst_vs_pick_one_node_oracle(self):
        """Randomized priority/PDB clusters with plain resource pods (no
        affinity ⇒ the conservative reblock bit never fires): the fused
        what-if must reproduce the reference exactly — selectVictimsOnNode
        (PDB-blocked reprieved first, then priority-descending) and
        pickOneNodeForPreemption's five lexicographic criteria."""
        import jax
        import jax.numpy as jnp

        from kubernetes_tpu.ops.lattice import (
            build_cycle, default_engine_config)
        from kubernetes_tpu.ops.preempt import preempt_batch

        I32MAX = 2**31 - 1

        def oracle(preemptor, nodes, existing, pdb_blocked):
            """Host replay of generic_scheduler.go:903/:1125 for
            resource-only pods."""
            per_node = {}
            for n in nodes:
                pot = [e for e in existing
                       if e.node_name == n.name
                       and e.priority < preemptor.priority]
                others = [e for e in existing
                          if e.node_name == n.name and e not in pot]

                def fits(group):
                    cpu = sum(e.requests.milli_cpu for e in group)
                    mem = sum(e.requests.memory_kib for e in group)
                    return (cpu + preemptor.requests.milli_cpu
                            <= n.allocatable.milli_cpu
                            and mem + preemptor.requests.memory_kib
                            <= n.allocatable.memory_kib
                            and len(group) + 1 <= n.allocatable.pods)

                if not fits(others):
                    continue  # not a candidate even with every victim gone
                kept = list(others)
                victims = []
                # reprieve order: PDB-blocked first, then priority desc,
                # then original index asc (the device lexsort's order)
                for v in sorted(pot, key=lambda e: (
                        not pdb_blocked.get(e.key, False),
                        -e.priority, e.creation_index)):
                    if fits(kept + [v]):
                        kept.append(v)
                    else:
                        victims.append(v)
                if not victims:
                    victims = []
                per_node[n.name] = victims
            if not per_node:
                return None, set()
            # pickOneNode: five keys
            def choice_key(name):
                v = per_node[name]
                npdb = sum(1 for x in v if pdb_blocked.get(x.key, False))
                maxp = max((x.priority for x in v), default=-I32MAX)
                sump = sum(x.priority for x in v)
                est = min((x.creation_index for x in v
                           if x.priority == maxp), default=I32MAX)
                return (npdb, maxp, sump, len(v), -est,
                        [n.name for n in nodes].index(name))
            best = min(per_node, key=choice_key)
            return best, {x.key for x in per_node[best]}

        rng = random.Random(11)
        for trial in range(8):
            n_nodes = rng.randint(2, 4)
            nodes = [mknode(f"n{i}", cpu=2) for i in range(n_nodes)]
            existing = [bound(f"e{i}", f"n{rng.randrange(n_nodes)}",
                              cpu=rng.choice(["300m", "700m", "1100m"]),
                              priority=rng.randrange(5), idx=i)
                        for i in range(rng.randint(3, 8))]
            pdb = {e.key: rng.random() < 0.3 for e in existing}
            pending = [Pod(name="vip", priority=50,
                           requests=Resources.make(cpu="1500m",
                                                   memory="128Mi"),
                           creation_index=99)]
            _cache, _enc, snap, keys = self._snapshot(nodes, existing,
                                                      pending)
            uk, ev = keys
            cyc = build_cycle(snap.tables, snap.existing, uk, ev,
                              snap.dims.D, jnp.float32(1.0),
                              default_engine_config())
            pdb_arr = np.zeros((snap.existing.valid.shape[0],), bool)
            for i, key in enumerate(snap.existing_keys):
                pdb_arr[i] = pdb.get(key, False)
            res = preempt_batch(snap.tables, cyc, snap.existing,
                                snap.pending.cls[:1],
                                snap.pending.node_name_req[:1],
                                snap.pending.priority[:1], snap.dims.D,
                                jnp.asarray(pdb_arr))
            node_idx = int(jax.device_get(res.node)[0])
            got_node = snap.node_order[node_idx] if node_idx >= 0 else None
            vmask = np.asarray(jax.device_get(res.victims)[0])
            got_victims = {snap.existing_keys[i]
                           for i in np.flatnonzero(
                               vmask[: len(snap.existing_keys)])}
            want_node, want_victims = oracle(pending[0], nodes, existing,
                                             pdb)
            assert got_node == want_node, (
                f"trial {trial}: node {got_node} != oracle {want_node}")
            assert got_victims == want_victims, (
                f"trial {trial}: victims {got_victims} != {want_victims}")

    @pytest.mark.parametrize("case", sorted(_WHAT_IF_PINNED))
    def test_what_if_result_pinned(self, case):
        """PreemptResult of every lane — node, victims, n_candidates,
        n_pdb_violations — as the what-if gave it when its survivors' term
        counts were scattered pod by pod (recorded on that tree): the
        histogram-and-product seeds (interpod.class_node_hist) hand the
        affinity, anti-affinity and spread filters the same counts."""
        import jax
        import jax.numpy as jnp

        from kubernetes_tpu.ops.lattice import (
            build_cycle, default_engine_config)
        from kubernetes_tpu.ops.preempt import preempt_batch

        nodes, existing, pending, pdb = _what_if_case(case)
        _cache, _enc, snap, (uk, ev) = self._snapshot(nodes, existing,
                                                      pending)
        cyc = build_cycle(snap.tables, snap.existing, uk, ev, snap.dims.D,
                          jnp.float32(1.0), default_engine_config())
        pdb_arr = np.array([k in pdb for k in snap.existing_keys]
                           + [False] * (snap.existing.valid.shape[0]
                                        - len(snap.existing_keys)))
        res = jax.device_get(preempt_batch(
            snap.tables, cyc, snap.existing, snap.pending.cls,
            snap.pending.node_name_req, snap.pending.priority, snap.dims.D,
            jnp.asarray(pdb_arr)))
        got = []
        for lane in range(len(pending)):
            n = int(res.node[lane])
            got.append((snap.node_order[n] if n >= 0 else None,
                        sorted(snap.existing_keys[i] for i in
                               np.flatnonzero(res.victims[lane])),
                        int(res.n_candidates[lane]),
                        int(res.n_pdb_violations[lane])))
        assert got == _WHAT_IF_PINNED[case]

    def test_scheduler_burst_evicts_and_nominates(self):
        """End-to-end through Scheduler.schedule_pending: several failed
        priority pods preempt in ONE burst — victims evicted, preemptors
        nominated on distinct nodes and requeued, all in the one pass."""
        from kubernetes_tpu.sched.preemption import Preemptor
        from kubernetes_tpu.sched.scheduler import (
            RecordingBinder, Scheduler)

        class FakeClock:
            t = 0.0

            def __call__(self):
                return self.t

        clock = FakeClock()
        s = Scheduler(binder=RecordingBinder(), clock=clock,
                      preemptor=Preemptor())
        for i in range(2):
            s.on_node_add(mknode(f"n{i}", cpu=1))
            s.on_pod_add(bound(f"victim{i}", f"n{i}", cpu="800m",
                               priority=0, idx=i))
        for i in range(2):
            s.on_pod_add(Pod(name=f"vip{i}", priority=100,
                             requests=Resources.make(cpu="800m",
                                                     memory="128Mi"),
                             creation_index=10 + i))
        st = s.schedule_pending()
        assert st.scheduled == 0
        # the burst evaluates both vips (one template, one lane) against
        # the SAME snapshot; the hand-out gives the lane's first replica
        # the best node and its second the next one (PR 41: before it the
        # second was told to retry and took a pass of its own)
        assert len(s.preemptor.evictor.evicted) == 2
        assert s.preemptor.successes == 2
        assert {s.queue.nominated_node(f"default/vip{i}")
                for i in range(2)} == {"n0", "n1"}
        assert s.preemptor.last_pass["preempt_nodes_handed_out"] == 2
        # the very next wave binds both on the freed nodes
        clock.t += 10.0
        assigned = s.schedule_pending().assignments
        assert set(assigned) == {"default/vip0", "default/vip1"}
        assert set(assigned.values()) == {"n0", "n1"}
        assert set(s.preemptor.evictor.evicted) == {"default/victim0",
                                                    "default/victim1"}


    def test_replicas_of_one_template_share_one_what_if(self, monkeypatch):
        """Preemptors that agree on (class, nodeName pin, priority) get the
        identical what-if against the pre-burst snapshot, so a burst of 40
        replicas of two templates is ONE 8-lane dispatch, not five — and
        the lane's replicas take its candidate nodes in order: two nodes,
        two nominations, the other 18 replicas (and the template no node
        can hold) unschedulable."""
        import kubernetes_tpu.sched.preemption as pm
        from kubernetes_tpu.sched.scheduler import (
            RecordingBinder, Scheduler)

        calls = []
        real = pm._preempt

        def counting(*a, **k):
            calls.append(1)
            return real(*a, **k)

        monkeypatch.setattr(pm, "_preempt", counting)
        s = Scheduler(binder=RecordingBinder(), clock=lambda: 0.0,
                      preemptor=pm.Preemptor())
        s.prewarmer.enabled = False  # the counted jit path, not an AOT one
        for i in range(2):
            s.on_node_add(mknode(f"n{i}", cpu=1))
            s.on_pod_add(bound(f"victim{i}", f"n{i}", cpu="800m",
                               priority=0, idx=i))
        for i in range(40):
            # two templates: evictable space for one, none for the other
            big = i % 2 == 1
            s.on_pod_add(Pod(
                name=f"vip{i}", priority=100,
                requests=Resources.make(cpu="8" if big else "800m",
                                        memory="128Mi"),
                creation_index=10 + i))
        st = s.schedule_pending()
        assert st.scheduled == 0
        assert len(calls) == 1
        assert s.preemptor.attempts == 40
        assert s.preemptor.successes == 2
        assert len(s.preemptor.evictor.evicted) == 2
        assert s.preemptor.last_pass == {
            **s.preemptor.last_pass, "preempt_lanes": 2,
            "preempt_preemptors": 40, "preempt_dispatches": 1,
            "preempt_nodes_handed_out": 2, "preempt_nominated": 2,
            "preempt_victims": 2, "preempt_retry_soon": 0}
        assert st.unschedulable == 38


class TestSatellites:
    def test_csr_stamping_keyed_on_path_not_kind(self):
        """POSTing to the CSR collection with `kind` omitted must still get
        the authenticated identity stamped (apiserver/server.py keys the
        stamp on the resolved resource path — body kind is client data)."""
        import json as _json
        import urllib.request

        from kubernetes_tpu.apiserver import APIServer, HTTPGateway
        from kubernetes_tpu.apiserver.auth import (
            AuthGate, TokenAuthenticator)

        api = APIServer()
        ta = TokenAuthenticator()
        ta.add("tok", "eve", ("system:unprivileged",))
        gw = HTTPGateway(api, auth_gate=AuthGate(
            authenticator=ta, allow_anonymous=False)).start()
        try:
            body = _json.dumps({
                # kind/apiVersion deliberately omitted — the registry
                # defaults them AFTER auth; the stamp must not care
                "metadata": {"name": "forged"},
                "spec": {"request": "eA==",
                         "username": "system:bootstrap:evil",
                         "groups": ["system:bootstrappers"]}}).encode()
            req = urllib.request.Request(
                gw.url + "/apis/certificates.k8s.io/v1beta1/"
                         "certificatesigningrequests",
                data=body, method="POST",
                headers={"Authorization": "Bearer tok",
                         "Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                out = _json.loads(r.read())
            assert out["spec"]["username"] == "eve"
            assert "system:unprivileged" in out["spec"]["groups"]
            assert "system:bootstrappers" not in out["spec"]["groups"]
        finally:
            gw.stop()

    def test_csr_spec_immutable_on_update_and_patch(self):
        """CSR spec is pinned on update/patch (csrStrategy.PrepareForUpdate):
        a forged spec swap after create silently keeps the stored spec."""
        from kubernetes_tpu.apiserver import APIServer

        api = APIServer()
        st = api.store("certificates.k8s.io", "certificatesigningrequests")
        st.create("", {"metadata": {"name": "c1"},
                       "spec": {"request": "eA==", "username": "honest",
                                "groups": ["g1"]}})
        cur = st.get("", "c1")
        cur["spec"] = {"request": "eA==", "username": "forged",
                       "groups": ["system:bootstrappers"]}
        out = st.update("", "c1", cur)
        assert out["spec"]["username"] == "honest"
        out = st.patch("", "c1", {"spec": {"username": "forged2"}},
                       patch_type="merge")
        assert out["spec"]["username"] == "honest"
        assert out["spec"]["groups"] == ["g1"]

    def test_rbac_confines_bootstrap_tokens(self):
        """The authenticated topology's seeded RBAC: bootstrappers may
        create/get CSRs and read kube-public/cluster-info, but CANNOT read
        the kube-system CA secret; system:masters can do everything."""
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.apiserver.auth import (
            Attributes, RBACAuthorizer, UserInfo)
        from kubernetes_tpu.cli.cluster import Cluster, ClusterConfig

        c = Cluster(ClusterConfig())
        c.api = APIServer()
        c._seed_rbac_policy()
        authz = RBACAuthorizer(c.api)
        joiner = UserInfo("system:bootstrap:abc",
                          ("system:bootstrappers",))
        admin = UserInfo("kubernetes-admin", ("system:masters",))

        def allowed(user, verb, group, resource, ns="", name=""):
            return authz.authorize(Attributes(user, verb, group, resource,
                                              ns, name))

        assert allowed(joiner, "create", "certificates.k8s.io",
                       "certificatesigningrequests")
        assert allowed(joiner, "get", "certificates.k8s.io",
                       "certificatesigningrequests", name="node-csr-x")
        assert allowed(joiner, "get", "", "configmaps", "kube-public",
                       "cluster-info")
        assert not allowed(joiner, "get", "", "secrets", "kube-system",
                           "cluster-ca")
        assert not allowed(joiner, "list", "", "secrets", "kube-system")
        assert not allowed(joiner, "create", "", "pods", "default")
        assert allowed(admin, "get", "", "secrets", "kube-system",
                       "cluster-ca")
        assert allowed(admin, "delete", "apps", "deployments", "prod", "x")

    def test_json_patch_missing_value_is_400(self):
        """RFC 6902: add/replace/test without a `value` member is a 400,
        never a silent null write."""
        from kubernetes_tpu.machinery import errors
        from kubernetes_tpu.machinery.strategicpatch import json_patch

        doc = {"spec": {"replicas": 3}}
        for op in ("add", "replace", "test"):
            with pytest.raises(errors.StatusError) as ei:
                json_patch(doc, [{"op": op, "path": "/spec/replicas"}])
            assert ei.value.code == 400
        # the legal explicit-null value still works
        out = json_patch(doc, [{"op": "replace", "path": "/spec/replicas",
                                "value": None}])
        assert out["spec"]["replicas"] is None

    def test_healthz_requeued_event_survives_sync(self):
        """An event arriving after sync() popped _pending must leave
        /healthz primed: a wedged loop then goes 503 instead of 200-forever
        (proxy/healthcheck.py + proxier.sync re-stamp)."""
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.client import Client
        from kubernetes_tpu.client.informers import InformerFactory
        from kubernetes_tpu.proxy.proxier import Proxier

        class FakeClock:
            t = 100.0

            def __call__(self):
                return self.t

        class FakeHealthz:
            def __init__(self, clock):
                self.clock = clock
                self._queued = 0.0
                self._updated = 0.0

            def queued_update(self):
                if self._queued == 0.0:
                    self._queued = self.clock()

            def updated(self):
                self._updated = self.clock()
                self._queued = 0.0

        api = APIServer()
        client = Client.local(api)
        clock = FakeClock()
        hz = FakeHealthz(clock)
        factory = InformerFactory(client)
        proxier = Proxier(client, factory, healthz=hz)
        client.services.create({
            "apiVersion": "v1", "kind": "Service",
            "metadata": {"name": "svc", "namespace": "default"},
            "spec": {"ports": [{"port": 80}]}})
        factory.start()
        factory.wait_for_sync()
        assert hz._queued > 0.0
        proxier.sync()
        assert hz._queued == 0.0   # clean pass clears the stamp

        # an event that lands AFTER the pass popped _pending: simulate by
        # injecting into _pending after updated() would have cleared it
        with proxier._pending_mu:
            proxier._pending.add("default/svc")
        hz.queued_update()
        clock.t = 101.0
        # the sync pass programs it and the re-stamp logic must keep the
        # stamp ONLY if something is still pending afterwards
        proxier.sync()
        assert hz._queued == 0.0
        # now wedge: event arrives mid-pass (after pop) — emulate by
        # patching sync's tail: pending non-empty when updated() runs
        orig_updated = hz.updated

        def updated_with_race():
            with proxier._pending_mu:
                proxier._pending.add("default/svc")
            orig_updated()
        hz.updated = updated_with_race
        proxier.sync()
        assert hz._queued > 0.0, (
            "queued_update stamp lost: a wedged sync loop would report "
            "healthy forever")
        factory.stop()
