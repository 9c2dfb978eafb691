"""Fleet serving (kubernetes_tpu/fleet/): K virtual tenant clusters per
vmap'd tick with tensorized DRF quotas.

The load-bearing claims, each held by a test class:
  * stacking/padding — tenants share one fleet bucket; inert pad tenants
    (and inert node rows inside small tenants) can never admit a pod;
  * DRF clamp goldens — the quota pre-mask admits exactly the prefix the
    tenant's dominant-share headroom funds, in queue order;
  * K=1 degenerate — a one-tenant fleet tick places bit-identically to the
    plain single-cluster Scheduler;
  * bit-equality — every tenant of a K-tenant tick places bit-identically
    to running that tenant alone under the same clamp;
  * per-tenant ledger replay — a crash mid-commit leaves an intent ONLY in
    the crashed tenant's namespace, and replay touches only it;
  * tenant-storm chaos — one tenant's injected watch storm degrades only
    that tenant's stats; fleet-wide zero lost/double-bound.
"""

import os

import pytest

from kubernetes_tpu.api.types import Node, Pod, Resources
from kubernetes_tpu.fleet import FleetServer, tenant_ledger
from kubernetes_tpu.fleet.tables import (
    FleetStack, empty_tenant_block, fleet_dims, stack_blocks)
from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler
from kubernetes_tpu.state.dims import Dims

pytestmark = pytest.mark.fleet


def mknode(i, cpu="8"):
    return Node(name=f"n{i}",
                allocatable=Resources.make(cpu=cpu, memory="16Gi",
                                           pods=110))


def feed(t, tn, n, cpu="100m", prio=None):
    for i in range(n):
        t.on_pod_add(Pod(name=f"{tn}-p{i}",
                         requests=Resources.make(cpu=cpu, memory="8Mi"),
                         priority=(prio(i) if prio else 0),
                         creation_index=i))


def det_server(**kw):
    """A FleetServer on a deterministic clock (1 virtual second per tick):
    RecordingBinder has no informer confirming binds, so on a slow box a
    cold compile longer than the 30 s assume TTL would expire assumed pods
    mid-run and re-free a clamped tenant's usage — a timing artifact, not
    scheduler behavior (the mesh bench stage documents the same fix)."""
    clk = {"t": 0.0}
    srv = FleetServer(clock=lambda: clk["t"], **kw)
    orig_tick = srv.tick

    def ticking(now=None):
        out = orig_tick(now)
        clk["t"] += 1.0
        return out

    srv.tick = ticking
    return srv


def build_fleet(spec, batch_size=16, mesh=None, storage=None, **kw):
    """spec: [(name, n_nodes, n_pods, quota)] → (server, {name: binder}).
    Extra kwargs (node_shards, engines, base_dims, …) pass to FleetServer."""
    srv = det_server(batch_size=batch_size, mesh=mesh, storage=storage, **kw)
    binders = {}
    for name, n_nodes, n_pods, quota in spec:
        b = RecordingBinder()
        binders[name] = b
        t = srv.add_tenant(name, binder=b, quota=quota)
        for i in range(n_nodes):
            t.on_node_add(mknode(i))
        feed(t, name, n_pods)
    return srv, binders


class TestFleetDims:
    def test_union_is_fieldwise_max(self):
        a = Dims().grown_for(N=32, P=8)
        b = Dims().grown_for(N=8, E=64)
        u = a.union(b)
        assert u.N == a.N and u.E == b.E and u.P == a.P
        # union never shrinks either side
        assert u == u.union(a) == u.union(b)

    def test_union_ors_node_name_flag(self):
        from dataclasses import replace

        a = replace(Dims(), has_node_name=True)
        assert a.union(Dims()).has_node_name
        assert Dims().union(a).has_node_name

    def test_fleet_dims_clears_routing_flag(self):
        from dataclasses import replace

        d = fleet_dims([replace(Dims().grown_for(N=32),
                                has_node_name=True)])
        assert not d.has_node_name
        assert d.N == 32


class TestStacking:
    def test_stacked_shapes_carry_leading_tenant_axis(self):
        d = Dims().grown_for(N=16, P=8, E=8)
        blocks = [empty_tenant_block(d) for _ in range(3)]
        stacked = stack_blocks(blocks)
        tables, pending, existing, (uk, ev) = stacked
        assert tables.nodes.alloc.shape[0] == 3
        assert pending.valid.shape == (3, d.P)
        assert existing.valid.shape == (3, d.E)
        assert uk.shape == (3,)

    @pytest.mark.parametrize("engine", ["waves", "scan"])
    def test_pad_tenant_is_inert(self, engine):
        """An empty-cluster pad tenant admits nothing through either engine —
        the tenant-axis analog of pad_node_tables' zero-phantom proof."""
        import jax
        import jax.numpy as jnp

        from kubernetes_tpu.fleet.cycle import _fleet_cycle_impl
        from kubernetes_tpu.ops.lattice import default_engine_config

        d = Dims().grown_for(N=8, P=8, E=8)
        blocks = [empty_tenant_block(d) for _ in range(2)]
        tables, pending, existing, keys = jax.device_put(
            stack_blocks(blocks))
        quota = jnp.ones((2,), jnp.float32)
        res = _fleet_cycle_impl(tables, pending, keys, d.D, existing,
                                engine, quota, jnp.float32(1.0),
                                default_engine_config())
        assert not bool(res.feasible.any())
        assert int((res.node >= 0).sum()) == 0

    def test_unchanged_tenant_skips_patch_changed_one_donates(self):
        srv, binders = build_fleet(
            [("a", 2, 4, 1.0), ("b", 2, 0, 1.0)], batch_size=2)
        srv.tick()
        assert srv.stack.full_restacks >= 1
        donated0 = srv.stack.donated_patches
        restacks0 = srv.stack.full_restacks
        srv.tick()  # a changed (pods bound), b is identical
        # no shape change → no restack; a's row went through the donated
        # scatter; donation never silently copied
        assert srv.stack.full_restacks == restacks0
        assert srv.stack.donated_patches > donated0
        assert srv.stack.donation_failures == 0


class TestDRFQuota:
    """Clamp goldens on a hand-computable tenant: 2 nodes × 2 cpu → 4000m
    capacity; the dominant resource is cpu by construction (memory/pods
    shares are orders smaller)."""

    def _tenant_tables(self, existing_cpu_m=0, pending=8,
                       pending_cpu="500m", prio=None):
        import jax

        from kubernetes_tpu.sched.cycle import snapshot_with_keys
        from kubernetes_tpu.state.cache import SchedulerCache
        from kubernetes_tpu.state.encode import Encoder

        cache = SchedulerCache()
        enc = Encoder()
        for i in range(2):
            cache.add_node(mknode(i, cpu="2"))
        if existing_cpu_m:
            cache.add_pod(Pod(
                name="busy", node_name="n0",
                requests=Resources.make(cpu=f"{existing_cpu_m}m"),
                creation_index=0))
        pods = [Pod(name=f"p{i}",
                    requests=Resources.make(cpu=pending_cpu),
                    priority=(prio(i) if prio else 0),
                    creation_index=i + 1)
                for i in range(pending)]
        snap, keys = snapshot_with_keys(cache, enc, pods, None)
        return snap, pods

    def test_share_and_prefix_waterfill(self):
        import numpy as np

        from kubernetes_tpu.fleet.quota import drf_admission_row

        # used 1000m of 4000m → share 0.25; quota 0.5 leaves 0.25 headroom
        # = 1000m = exactly 2 pods of 500m
        snap, pods = self._tenant_tables(existing_cpu_m=1000, pending=6)
        import jax.numpy as jnp

        mask, share, dom = drf_admission_row(snap.tables, snap.pending,
                                             jnp.float32(0.5))
        assert abs(float(share) - 0.25) < 1e-5
        m = np.asarray(mask)[: len(pods)]
        assert m.sum() == 2
        # queue order = creation order here → the FIRST two pods admit
        assert m[:2].all() and not m[2:].any()

    def test_at_quota_tenant_is_inert(self):
        import numpy as np

        import jax.numpy as jnp

        from kubernetes_tpu.fleet.quota import drf_admission_row

        snap, pods = self._tenant_tables(existing_cpu_m=2000, pending=4)
        mask, share, _ = drf_admission_row(snap.tables, snap.pending,
                                           jnp.float32(0.5))
        assert float(share) >= 0.5 - 1e-6
        assert not np.asarray(mask).any()

    def test_priority_orders_the_waterfill(self):
        """Headroom funds one pod; the HIGHEST-priority pending pod gets
        it (queue order: priority desc, creation asc)."""
        import numpy as np

        import jax.numpy as jnp

        from kubernetes_tpu.fleet.quota import drf_admission_row

        snap, pods = self._tenant_tables(
            existing_cpu_m=1500, pending=4,
            prio=lambda i: 100 if i == 3 else 0)  # last pod outranks all
        mask, _, _ = drf_admission_row(snap.tables, snap.pending,
                                       jnp.float32(0.5))
        m = np.asarray(mask)[: len(pods)]
        assert m[3] and m.sum() == 1

    def test_violation_headroom_invariant(self):
        import jax.numpy as jnp

        from kubernetes_tpu.fleet.quota import violation_headroom

        share = jnp.float32([0.2, 0.9])
        quota = jnp.float32([0.5, 0.5])
        dom = jnp.float32([[0.1, 0.1], [0.1, 0.1]])
        ok = jnp.asarray([[True, True], [False, False]])
        bad = jnp.asarray([[True, True], [True, False]])
        assert not bool(violation_headroom(share, dom, ok, quota).any())
        assert bool(violation_headroom(share, dom, bad, quota)[1])


class TestFleetTick:
    def test_three_tenants_one_dispatch_per_tick(self):
        srv, binders = build_fleet(
            [("a", 4, 6, 1.0), ("b", 4, 3, 1.0), ("c", 4, 9, 1.0)])
        total = srv.run_until_idle(max_ticks=6)
        assert srv.max_dispatches_per_tick == 1
        assert total.cross_tenant_placements == 0
        assert total.drf_violations == 0
        for name, n in (("a", 6), ("b", 3), ("c", 9)):
            assert len(binders[name].bound) == n
            assert total.per_tenant[name].scheduled == n

    def test_quota_clamped_tenant_defers_not_fails(self):
        # 4 nodes × 8 cpu = 32000m; quota 0.25 funds 8000m = 16 pods of
        # 500m; the remaining 8 stay QUEUED (requeued, never
        # unschedulable, never lost)
        srv2 = det_server(batch_size=32)
        b2 = {}
        for name, quota in (("clamped", 0.25), ("free", 1.0)):
            b = RecordingBinder()
            b2[name] = b
            t = srv2.add_tenant(name, binder=b, quota=quota)
            for i in range(4):
                t.on_node_add(mknode(i))
            feed(t, name, 24 if name == "clamped" else 10, cpu="500m")
        total = srv2.run_until_idle(max_ticks=10)
        assert len(b2["clamped"].bound) == 16
        assert len(b2["free"].bound) == 10
        st = total.per_tenant["clamped"]
        assert st.requeued > 0 and st.unschedulable == 0
        assert total.drf_violations == 0
        # nothing lost: every unbound pod is still in a queue lane
        q = srv2.tenant("clamped").sched.queue.lengths()
        assert sum(q) == 24 - 16

    def test_fleet_grows_when_one_tenant_grows(self):
        """The shared-bucket contract: tenant B joining nodes past the
        fleet N bucket forces EVERY tenant's next snapshot up to the new
        union — and the tick keeps working across the growth."""
        srv, binders = build_fleet([("a", 2, 2, 1.0), ("b", 2, 2, 1.0)],
                                   batch_size=4)
        srv.tick()
        d0 = srv._fleet_dims
        tb = srv.tenant("b")
        for i in range(2, d0.N + 2):   # grow b past the shared bucket
            tb.on_node_add(mknode(i))
        feed(tb, "b2", 2)
        feed(srv.tenant("a"), "a2", 2)
        srv.run_until_idle(max_ticks=6)
        assert srv._fleet_dims.N > d0.N
        assert len(binders["a"].bound) == 4
        assert len(binders["b"].bound) == 4


class TestK1Degenerate:
    def test_single_tenant_fleet_matches_plain_scheduler(self):
        base = Dims().grown_for(N=8, P=16, E=16)
        pods = [Pod(name=f"p{i}", requests=Resources.make(
            cpu="300m", memory="64Mi"), creation_index=i)
            for i in range(12)]

        srv = det_server(batch_size=16, base_dims=base)
        fb = RecordingBinder()
        t = srv.add_tenant("solo", binder=fb, quota=1.0)
        for i in range(4):
            t.on_node_add(mknode(i))
        for p in pods:
            t.on_pod_add(p)
        srv.run_until_idle(max_ticks=4)

        sb = RecordingBinder()
        s = Scheduler(binder=sb, batch_size=16, base_dims=base, mesh=0)
        for i in range(4):
            s.on_node_add(mknode(i))
        for p in pods:
            s.on_pod_add(p)
        s.run_until_idle()
        assert sorted(fb.bound) == sorted(sb.bound)


class TestBitEquality:
    def test_each_tenant_matches_its_solo_run(self):
        """K-tenant tick vs running each tenant alone (same clamp inputs):
        bound (pod, node) sets must be identical, clamped tenant
        included."""
        spec = [("a", 4, 11, 1.0), ("b", 3, 7, 0.25), ("c", 5, 13, 1.0)]

        def run(tenants):
            srv = det_server(batch_size=8)
            binders = {}
            for name, n_nodes, n_pods, quota in tenants:
                b = RecordingBinder()
                binders[name] = b
                t = srv.add_tenant(name, binder=b, quota=quota)
                for i in range(n_nodes):
                    t.on_node_add(mknode(i, cpu="2"))
                feed(t, name, n_pods, cpu="500m")
            srv.run_until_idle(max_ticks=10)
            return binders

        together = run(spec)
        for entry in spec:
            alone = run([entry])
            name = entry[0]
            assert sorted(together[name].bound) == \
                sorted(alone[name].bound), name


class TestTenantLedger:
    def test_namespaced_prefixes_are_disjoint(self):
        from kubernetes_tpu.apiserver import APIServer

        api = APIServer()
        try:
            la = tenant_ledger(api.storage, "alpha")
            lb = tenant_ledger(api.storage, "beta")
            ia = la.write_intent(cycle=1, token=0, bindings={"x": "n0"})
            assert ia.key.startswith(
                "/registry/ktpu.io/bindintents/alpha/default-scheduler/")
            assert len(la.unretired()) == 1
            assert len(lb.unretired()) == 0   # beta never sees alpha's
            la.retire(ia)
            assert len(la.unretired()) == 0
        finally:
            api.close()

    @pytest.mark.chaos
    def test_crash_replay_touches_only_the_crashed_tenant(self):
        """Kill the fleet at post_bind (Bindings committed, intent NOT
        retired — the PR 4 kill matrix's nastiest row, per tenant): the
        orphaned intent lives ONLY under the crashed tenant's namespace,
        and a fresh incarnation's recover() replays exactly it."""
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.utils import faultline
        from kubernetes_tpu.utils.faultline import InjectedCrash

        api = APIServer()
        try:
            faultline.install("proc.crash@post_bind:1")
            srv, binders = build_fleet(
                [("alpha", 2, 3, 1.0), ("beta", 2, 3, 1.0)],
                batch_size=8, storage=api.storage)
            with pytest.raises(InjectedCrash):
                srv.tick()
            faultline.uninstall()
            la = tenant_ledger(api.storage, "alpha")
            lb = tenant_ledger(api.storage, "beta")
            assert len(la.unretired()) == 1
            assert len(lb.unretired()) == 0

            srv2, b2 = build_fleet(
                [("alpha", 2, 3, 1.0), ("beta", 2, 3, 1.0)],
                batch_size=8, storage=api.storage)
            reports = srv2.recover()
            assert reports["alpha"].replayed_intents == 1
            assert reports["beta"].replayed_intents == 0
            assert len(la.unretired()) == 0
            srv2.run_until_idle(max_ticks=6)
            # exactly-once fleet-wide: every pod bound exactly once in the
            # new incarnation, none lost
            for name in ("alpha", "beta"):
                keys = [k for k, _ in b2[name].bound]
                assert len(keys) == 3 and len(set(keys)) == 3
        finally:
            faultline.uninstall()
            api.close()


class TestOneCommitStage:
    """The commit stage is one (`Scheduler.commit_wave`): a fleet tenant's
    share of a tick and a single-cluster wave come out of it alike, fault
    for fault — same CycleStats, same binder, same queue, same ledger."""

    @staticmethod
    def _wave(fleet: bool, fault: str, storage):
        class Refusing(RecordingBinder):
            def bind(self, pod, node_name):
                return False

        binder = Refusing() if fault == "breaker_opens_mid_wave" \
            else RecordingBinder()
        ledger = tenant_ledger(storage, "fleet" if fleet else "plain")
        written = []
        write = ledger.write_intent

        def spy(**kw):
            written.append(kw["bindings"])
            if fault == "intent_write_fails":
                raise OSError("ledger storage unavailable")
            return write(**kw)

        ledger.write_intent = spy
        if fleet:
            srv = det_server(batch_size=16)
            target = srv.add_tenant("t", binder=binder, ledger=ledger)
            sched = target.sched
        else:
            target = sched = Scheduler(binder=binder, ledger=ledger,
                                       batch_size=16, clock=lambda: 0.0)
        for i in range(2):
            target.on_node_add(mknode(i))
        feed(target, "t", 8)
        stats = srv.tick().per_tenant["t"] if fleet \
            else sched.schedule_pending()
        return stats, sched, binder, ledger, written

    @pytest.mark.parametrize("fault", ["intent_write_fails",
                                       "breaker_opens_mid_wave"])
    def test_tenant_and_plain_scheduler_agree(self, fault):
        import dataclasses

        from kubernetes_tpu.apiserver import APIServer

        api = APIServer()
        try:
            got = {fleet: self._wave(fleet, fault, api.storage)
                   for fleet in (False, True)}
            for fleet, (st, sched, binder, ledger, written) in got.items():
                assert st.attempted == 8 and st.scheduled == 0
                assert binder.bound == []
                assert len(written) == 1 and len(written[0]) == 8
                assert ledger.unretired() == []
                if fault == "intent_write_fails":
                    # no intent, no Binding: the whole wave is back in the
                    # active queue with no verdict and its attempts kept
                    # (a fleet tick counts those as requeued as well)
                    assert st.aborted == 8 and st.bind_errors == 0
                    assert st.requeued == (8 if fleet else 0)
                    assert sched.queue.lengths() == (8, 0, 0)
                    assert {a for _, a in sched.queue.pop_batch(16)} == {2}
                else:
                    # five refused Bindings open the breaker: they carry
                    # their verdict, the tail requeues promptly with none,
                    # and the intent that covered all eight is retired
                    assert st.bind_errors == 5 and st.requeued == 3
                    assert st.aborted == 0 and len(st.failed_keys) == 5
                    assert sched.queue.lengths()[0] == 3
                    assert sched.cache.counts()[1] == 0   # nothing assumed
            plain, tenant = (dataclasses.asdict(got[f][0])
                             for f in (False, True))
            for d in (plain, tenant):
                del d["cycle_seconds"], d["requeued"]   # checked above
            assert plain == tenant
            assert got[False][1].queue.depths() == got[True][1].queue.depths()
        finally:
            api.close()


class TestTenantStorm:
    @pytest.mark.chaos
    def test_storm_degrades_only_the_stormed_tenant(self):
        from kubernetes_tpu.utils import faultline

        faultline.install("tenant.storm@beta:1+")
        try:
            srv, binders = build_fleet(
                [("alpha", 4, 8, 1.0), ("beta", 4, 8, 1.0),
                 ("gamma", 4, 8, 1.0)])
            total = srv.run_until_idle(max_ticks=6)
            # the stormed tenant made no progress but LOST nothing
            assert len(binders["beta"].bound) == 0
            assert sum(srv.tenant("beta").sched.queue.lengths()) == 8
            assert total.per_tenant["beta"].degraded >= 1
            # the others are untouched: fully bound, zero degraded ticks,
            # no cross-tenant placements, no double binds
            for name in ("alpha", "gamma"):
                keys = [k for k, _ in binders[name].bound]
                assert len(keys) == 8 and len(set(keys)) == 8
                assert total.per_tenant[name].degraded == 0
            assert total.cross_tenant_placements == 0
            assert faultline.active().fired("tenant.storm") >= 1
        finally:
            faultline.uninstall()

    @pytest.mark.chaos
    def test_storm_recovery_rebinds_after_uninstall(self):
        from kubernetes_tpu.utils import faultline

        faultline.install("tenant.storm@beta:1")  # one-shot
        try:
            srv, binders = build_fleet(
                [("alpha", 4, 4, 1.0), ("beta", 4, 4, 1.0)])
            srv.run_until_idle(max_ticks=8)
            assert len(binders["alpha"].bound) == 4
            assert len(binders["beta"].bound) == 4  # recovered next tick
            assert srv.tenant("beta").storm_ticks == 1
        finally:
            faultline.uninstall()


@pytest.mark.mesh
class TestFleetMesh:
    def test_tenant_axis_sharded_tick_is_bit_equal(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")

        def run(mesh):
            srv, binders = build_fleet(
                [("a", 4, 7, 1.0), ("b", 4, 5, 1.0), ("c", 4, 9, 1.0)],
                mesh=mesh)
            srv.run_until_idle(max_ticks=6)
            return srv, binders

        srv_m, bm = run(mesh=8)
        assert srv_m.mesh is not None
        assert srv_m.stack.K == 8          # 3 tenants padded to the mesh
        assert srv_m.max_dispatches_per_tick == 1
        srv_s, bs = run(mesh=None)
        for name in ("a", "b", "c"):
            assert sorted(bm[name].bound) == sorted(bs[name].bound)


class TestPostPopFailure:
    def test_mid_tick_failure_requeues_every_popped_batch(self):
        """ANY failure between the batch pop and the dispatch result must
        hand every popped pod back to its queue (the scheduler may never
        lose a pod), then re-raise for visibility."""
        srv, binders = build_fleet([("a", 2, 5, 1.0), ("b", 2, 3, 1.0)],
                                   batch_size=8)

        def boom(*a, **kw):
            raise RuntimeError("injected post-pop failure")

        orig = srv._dispatch_tick
        srv._dispatch_tick = boom
        with pytest.raises(RuntimeError, match="post-pop"):
            srv.tick()
        for name, n in (("a", 5), ("b", 3)):
            q = srv.tenant(name).sched.queue
            assert sum(q.lengths()) == n, name
            assert len(binders[name].bound) == 0
        # the stack was dropped, and the next healthy tick recovers fully
        assert srv.stack.block is None
        srv._dispatch_tick = orig
        srv.run_until_idle(max_ticks=4)
        assert len(binders["a"].bound) == 5
        assert len(binders["b"].bound) == 3


class TestGangTenant:
    def test_gang_growth_restacks_every_tenant(self):
        """A gang-bearing tenant's solo wave binds enough pods to grow the
        fleet bucket MID-TICK (E doubles as the gang lands). Every tenant
        must then re-snapshot at the converged bucket before the restack —
        a per-gang-tenant refresh would leave the others at the old shapes
        and crash jnp.stack with the popped batches already consumed."""
        srv, binders = build_fleet(
            [("plain", 4, 6, 1.0), ("gang", 8, 0, 1.0)], batch_size=64)
        srv.tick()                       # resident stack at the small bucket
        t = srv.tenant("gang")
        for i in range(24):
            t.on_pod_add(Pod(name=f"gang-g{i}", pod_group="job",
                             min_member=24,
                             requests=Resources.make(cpu="100m",
                                                     memory="8Mi"),
                             creation_index=i))
        feed(srv.tenant("plain"), "plain2", 2)
        total = srv.run_until_idle(max_ticks=8)
        assert len(binders["gang"].bound) == 24
        assert len(binders["plain"].bound) == 8
        assert total.cross_tenant_placements == 0
        # nothing lost fleet-wide: every queue drained, no double binds
        for tn in srv.tenants.values():
            assert tn.sched.queue.lengths()[0] == 0
        for name in ("gang", "plain"):
            keys = [k for k, _ in binders[name].bound]
            assert len(keys) == len(set(keys))


@pytest.mark.mesh
class TestFleet2DMesh:
    """ISSUE 20 tentpole: the (tenant × node-shard) 2-D fleet mesh."""

    SPEC = [("a", 5, 7, 1.0), ("b", 3, 5, 1.0), ("c", 6, 9, 1.0)]

    def _run(self, mesh, node_shards=None):
        srv, binders = build_fleet(
            self.SPEC, mesh=mesh,
            **({} if node_shards is None else {"node_shards": node_shards}))
        srv.run_until_idle(max_ticks=8)
        return srv, binders

    def test_make_fleet_mesh_shapes(self):
        import jax

        from kubernetes_tpu.parallel.mesh import (
            NODE_AXIS, TENANT_AXIS, fleet_mesh_shape, make_fleet_mesh)

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        m1 = make_fleet_mesh(8)
        assert m1.axis_names == (TENANT_AXIS,)
        assert fleet_mesh_shape(m1) == (8, 1)
        m2 = make_fleet_mesh(8, node_shards=2)
        assert m2.axis_names == (TENANT_AXIS, NODE_AXIS)
        assert fleet_mesh_shape(m2) == (4, 2)
        with pytest.raises(ValueError):
            make_fleet_mesh(8, node_shards=3)   # must divide the width

    def test_pad_fleet_node_rows_are_inert(self):
        """Non-divisible N on the stacked [K, N, …] tree: every padded
        node row carries the pad_node_tables inert contract — invalid,
        unschedulable, name -1, zero capacity — per tenant."""
        from kubernetes_tpu.parallel.mesh import pad_fleet_node_tables

        d = Dims().grown_for(N=8, P=8, E=8)
        stacked = stack_blocks([empty_tenant_block(d) for _ in range(3)])
        tables = stacked[0]
        # carve N down to a non-divisible 6, then pad back for 4 shards
        import jax

        tables6 = jax.tree.map(
            lambda a: a[:, :6] if a.ndim >= 2 and a.shape[1] == d.N else a,
            tables)
        padded = pad_fleet_node_tables(tables6, 4)
        n = padded.nodes
        assert n.valid.shape[:2] == (3, 8)
        assert not bool(n.valid[:, 6:].any())
        assert bool(n.unschedulable[:, 6:].all())
        assert int(n.name_id[:, 6:].max()) == -1
        assert float(abs(n.alloc[:, 6:]).sum()) == 0.0
        assert float(abs(n.used[:, 6:]).sum()) == 0.0
        assert not bool(n.avoid[:, 6:].any())

    def test_2d_bit_equal_vs_1d_and_single_device(self):
        """K=3 tenants (pad tenant on the 4-wide tenant axis) with ragged
        per-tenant node counts on the 2-D mesh: placements bit-equal to
        the 1-D tenant mesh AND to the meshless run — zero phantom
        admissions onto pad tenants or pad node rows, one dispatch per
        tick throughout."""
        import jax

        from kubernetes_tpu.parallel.mesh import fleet_mesh_shape

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        srv2, b2 = self._run(mesh=8, node_shards=2)
        assert fleet_mesh_shape(srv2.mesh) == (4, 2)
        assert srv2.stack.K == 4              # 3 tenants + 1 pad tenant
        assert srv2.max_dispatches_per_tick == 1
        srv1, b1 = self._run(mesh=8)
        assert fleet_mesh_shape(srv1.mesh) == (8, 1)
        srv0, b0 = self._run(mesh=None)
        for name, n_nodes, n_pods, _ in self.SPEC:
            assert sorted(b2[name].bound) == sorted(b1[name].bound), name
            assert sorted(b2[name].bound) == sorted(b0[name].bound), name
            # every pod landed exactly once, on a REAL node of its own
            # tenant (a phantom admission would surface a pad row's -1
            # name or drop a pod)
            keys = [k for k, _ in b2[name].bound]
            assert len(keys) == n_pods and len(set(keys)) == n_pods
            real = {f"n{i}" for i in range(n_nodes)}
            assert {nn for _, nn in b2[name].bound} <= real

    def test_refresh_pads_nondivisible_k_and_n_together(self):
        """Direct-constructed dims whose N the node axis does not divide,
        AND a live K under the tenant width: refresh stacks inert pad
        TENANTS and inert pad NODE rows simultaneously, and keeps forcing
        the full restack (the patch path would scatter unpadded staging
        rows onto node-padded residents)."""
        import jax

        from dataclasses import replace as _replace

        from kubernetes_tpu.parallel.mesh import make_fleet_mesh

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        from types import SimpleNamespace

        mesh = make_fleet_mesh(8, node_shards=4)   # tenant width 2
        stack = FleetStack(mesh=mesh)
        d = _replace(Dims(), N=6, P=8, E=8)        # 6 % 4 != 0
        blk = empty_tenant_block(d)
        snaps = [SimpleNamespace(tables=blk[0], pending=blk[1],
                                 existing=blk[2])]  # K=1 < width 2
        kp = stack.refresh(snaps, [(0, 0)], d)
        assert kp == 2
        tables = stack.block[0]
        assert tables.nodes.valid.shape[:2] == (2, 8)   # K and N padded
        assert not bool(tables.nodes.valid.any())       # all rows inert
        restacks = stack.full_restacks
        stack.refresh(snaps, [(0, 0)], d)
        assert stack.full_restacks == restacks + 1      # patch path barred

    @pytest.mark.chaos
    def test_degrade_reform_under_2d_signature(self, monkeypatch):
        """TestDegradedBackend's drill on the 2-D mesh: backend loss drops
        the fleet mesh (degraded ticks serve via fallback, resident stack
        untouched), re-admission REFORMS the (tenant × node-shard) mesh —
        same 2-D signature — and the next ticks restack and drain with
        nothing lost or double-bound."""
        import jax

        from kubernetes_tpu.parallel.mesh import fleet_mesh_shape
        from kubernetes_tpu.utils import faultline

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        monkeypatch.setenv("KTPU_PROBE_BACKOFF", "0.05")
        srv, binders = build_fleet([("a", 2, 4, 1.0), ("b", 2, 4, 1.0)],
                                   mesh=8, node_shards=2)
        assert fleet_mesh_shape(srv.mesh) == (4, 2)
        srv.tick()
        assert srv.stack.block is not None
        faultline.install("device.error@probe:1+")   # pin re-admission off
        try:
            srv.supervisor._mark_unhealthy("injected backend loss")
            assert srv.mesh_state.mesh is None       # dropped, not narrowed
            feed(srv.tenant("a"), "a2", 3)
            tk = srv.tick()                          # degraded, fallback
            assert srv.mesh is None                  # adopted the drop
            assert tk.per_tenant["a"].scheduled >= 1
        finally:
            faultline.uninstall()
        srv.supervisor._readmit()
        prober = srv.supervisor._prober
        if prober is not None:
            prober.join(timeout=10)
        feed(srv.tenant("b"), "b2", 2)
        srv.run_until_idle(max_ticks=4)
        # the reformed mesh is 2-D again and the server adopted it
        assert srv.mesh is srv.mesh_state.mesh
        assert fleet_mesh_shape(srv.mesh) == (4, 2)
        assert len(binders["a"].bound) == 7
        assert len(binders["b"].bound) == 6
        for name in ("a", "b"):
            keys = [k for k, _ in binders[name].bound]
            assert len(keys) == len(set(keys))


class TestDegradedBackend:
    @pytest.mark.chaos
    def test_degraded_tick_never_touches_resident_stack(self, monkeypatch):
        """Backend loss mid-fleet: the degraded tick must serve every
        tenant through the fallback WITHOUT scattering onto (or donating)
        the resident stacked buffers — they may live on the lost backend
        or still be held by an abandoned worker. Re-admission full-restacks
        onto fresh buffers."""
        from kubernetes_tpu.utils import faultline

        monkeypatch.setenv("KTPU_PROBE_BACKOFF", "0.05")
        srv, binders = build_fleet([("a", 2, 4, 1.0), ("b", 2, 4, 1.0)])
        srv.tick()
        assert srv.stack.block is not None
        pre_restacks = srv.stack.full_restacks
        faultline.install("device.error@probe:1+")   # pin re-admission off
        try:
            srv.supervisor._mark_unhealthy("injected backend loss")
            feed(srv.tenant("a"), "a2", 3)
            tk = srv.tick()
            # the fallback served the tick; the resident stack was dropped,
            # never patched
            assert srv.stack.block is None
            assert srv.stack.full_restacks == pre_restacks
            assert tk.per_tenant["a"].scheduled >= 1
        finally:
            faultline.uninstall()
        srv.supervisor._readmit()
        prober = srv.supervisor._prober
        if prober is not None:
            prober.join(timeout=10)   # park the probe loop before teardown
        feed(srv.tenant("b"), "b2", 2)
        srv.run_until_idle(max_ticks=4)
        assert srv.stack.full_restacks == pre_restacks + 1
        assert len(binders["a"].bound) == 7
        assert len(binders["b"].bound) == 6
        for name in ("a", "b"):
            keys = [k for k, _ in binders[name].bound]
            assert len(keys) == len(set(keys))
