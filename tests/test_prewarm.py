"""Compile-ahead on capacity-bucket growth (sched/prewarm.py).

The cold-compile cliff: crossing a Dims bucket recompiles the cycle program
(minutes at 2k+ nodes on a cold cache). The prewarmer must (a) build
abstract arguments whose shapes/pytree structure EXACTLY match the live
call — the fragile part, guarded here by actually compiling through the
production jit function — and (b) fire at the right occupancy, once per
signature, without ever blocking the scheduling loop.
"""

import threading
import time

import pytest

from kubernetes_tpu.api.types import Node, Pod, Resources
from kubernetes_tpu.sched.prewarm import BucketPrewarmer, abstract_cycle_args
from kubernetes_tpu.state.dims import Dims


def mknode(i, cpu="8"):
    return Node(name=f"n{i}",
                allocatable=Resources.make(cpu=cpu, memory="16Gi", pods=110))


class TestAbstractCompile:
    def test_abstract_args_compile_through_production_jit(self):
        """AOT-compiling from abstract shapes must succeed through
        _schedule_batch_impl itself — if the abstract pytree ever drifts
        from the live call's structure, this is the test that breaks."""
        from kubernetes_tpu.sched.cycle import _schedule_batch_impl

        d = Dims().grown_for(N=16, P=16, E=16)
        (tables, pending, keys, existing, hw, ecfg,
         gang) = abstract_cycle_args(d)
        compiled = _schedule_batch_impl.lower(
            tables, pending, keys, d.D, existing, "waves", hw, ecfg,
            (), (), gang).compile()
        assert compiled is not None

    def test_abstract_gang_args_compile_through_production_jit(self):
        """The gang-bearing trace (restart loop) must AOT-compile too —
        gang clusters cross buckets like any other."""
        from kubernetes_tpu.sched.cycle import _schedule_batch_impl

        d = Dims().grown_for(N=16, P=16, E=16, GR=8)
        (tables, pending, keys, existing, hw, ecfg,
         gang) = abstract_cycle_args(d, gang=True)
        assert gang is not None
        compiled = _schedule_batch_impl.lower(
            tables, pending, keys, d.D, existing, "waves", hw, ecfg,
            (), (), gang).compile()
        assert compiled is not None

    def test_prewarmed_signature_matches_live_call(self):
        """After warming dims d, a LIVE call at exactly d must hit the jit
        shape signature the warm built (same Dims → same array shapes)."""
        import jax.numpy as jnp

        from kubernetes_tpu.sched.cycle import (
            UNSCHEDULABLE_TAINT_KEY, _schedule_batch)
        from kubernetes_tpu.state.encode import Encoder

        nodes = [mknode(i) for i in range(4)]
        pods = [Pod(name=f"p{i}", requests=Resources.make(cpu="1"),
                    creation_index=i) for i in range(4)]
        enc = Encoder()
        enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
        enc.vocabs.label_vals.intern("")
        tables, ex, pe, d = enc.encode_cluster(nodes, [], pods, None)
        warm_args = abstract_cycle_args(d)
        live_shapes = [(a.shape, str(a.dtype))
                       for a in __import__("jax").tree.leaves(
                           (tables, pe, ex))]
        warm_shapes = [(a.shape, str(a.dtype))
                       for a in __import__("jax").tree.leaves(
                           (warm_args[0], warm_args[1], warm_args[3]))]
        assert warm_shapes == live_shapes

    def test_no_run_capacity_in_any_key_and_no_engine_variable(
            self, monkeypatch):
        """A cycle program's prewarm key is (dims, engine, extras, gang,
        fleet, mesh sig) and the supervisor's cycle signature (dims,
        engine, extras, gang, fleet): neither has a run-capacity slot, no
        entry point takes one, and `KTPU_ASSIGN` is read by nothing."""
        import inspect
        from dataclasses import replace

        from kubernetes_tpu.sched.cycle import (
            _schedule_batch, _schedule_batch_impl, plan_engine)
        from kubernetes_tpu.sched.supervisor import DispatchSupervisor

        monkeypatch.setenv("KTPU_ASSIGN", "scan")
        assert plan_engine(False) == "waves"
        d = Dims().grown_for(N=16, P=16, E=16)
        pw = BucketPrewarmer(threshold=0.8, min_axis=8)
        assert pw.ensure_warm(d, plan_engine(d.has_node_name))
        pw.wait(120)
        assert list(pw.compiled) == [
            (replace(d, has_node_name=False), "waves", (), False, None,
             None)]
        assert pw.warm_log == [(d, "waves")]
        sup = DispatchSupervisor(prewarmer=pw)
        sup.note_cycle_signature(d, "waves", (), False)
        assert sup._cycle_sig == (d, "waves", (), False, None)
        for fn in (pw.lookup, pw.observe, pw.rewarm, pw.ensure_warm,
                   pw._compile, sup.note_cycle_signature, plan_engine,
                   _schedule_batch, _schedule_batch_impl):
            assert not {"rc", "runs"} & set(inspect.signature(fn).parameters)


class TestTriggerPolicy:
    def _spy(self):
        calls = []
        ev = threading.Event()

        def fake_compile(d, engine, extras, gang, mesh=None, fleet=None):
            calls.append((d, engine, gang))
            ev.set()
        return calls, ev, fake_compile

    def test_fires_at_threshold_once_per_signature(self):
        calls, ev, fake = self._spy()
        pw = BucketPrewarmer(threshold=0.8, min_axis=8, compile_fn=fake)
        d = Dims().grown_for(N=16, E=16)
        pw.observe(d, n_nodes=4, n_existing=4)     # 25% — quiet
        assert not calls
        pw.observe(d, n_nodes=13, n_existing=4)    # 81% of N → fire
        assert ev.wait(5)
        pw.wait(5)
        assert len(calls) == 1
        target = calls[0][0]
        assert target.N > d.N                       # the NEXT bucket
        pw.observe(d, n_nodes=14, n_existing=4)    # same signature → no refire
        pw.wait(5)
        assert len(calls) == 1

    def test_multi_axis_crossing_warms_each_target(self):
        """Both axes near their boundary: successive cycles warm the N-only,
        E-only, AND joint targets — whichever the live path crosses first is
        covered (single compile in flight at a time)."""
        calls, _, fake = self._spy()
        pw = BucketPrewarmer(threshold=0.8, min_axis=8, compile_fn=fake)
        d = Dims().grown_for(N=16, E=16)
        for _ in range(5):
            pw.observe(d, n_nodes=14, n_existing=14)
            pw.wait(5)
        warmed = {(c[0].N, c[0].E) for c in calls}
        assert (32, 16) in warmed    # N-only
        assert (16, 32) in warmed    # E-only
        assert (32, 32) in warmed    # joint

    def test_gang_traces_warm_separately(self):
        """gang=True is part of the warmed key: a gang-bearing cluster warms
        the restart-loop trace, not (only) the plain one."""
        calls, ev, fake = self._spy()
        pw = BucketPrewarmer(threshold=0.8, min_axis=8, compile_fn=fake)
        d = Dims().grown_for(N=16)
        pw.observe(d, n_nodes=14, n_existing=1, gang=True)
        assert ev.wait(5)
        pw.wait(5)
        assert calls and calls[0][2] is True
        # same dims, plain trace → a separate warm
        pw.observe(d, n_nodes=14, n_existing=1, gang=False)
        pw.wait(5)
        assert len(calls) == 2 and calls[1][2] is False

    def test_small_axes_never_warm(self):
        calls, _, fake = self._spy()
        pw = BucketPrewarmer(threshold=0.8, min_axis=256, compile_fn=fake)
        d = Dims().grown_for(N=16, E=16)
        pw.observe(d, n_nodes=16, n_existing=16)   # 100% but tiny
        pw.wait(1)
        assert not calls

    def test_existing_axis_growth_fires(self):
        calls, ev, fake = self._spy()
        pw = BucketPrewarmer(threshold=0.8, min_axis=8, compile_fn=fake)
        d = Dims().grown_for(N=16, E=32)
        pw.observe(d, n_nodes=2, n_existing=30)    # 94% of E
        assert ev.wait(5)
        pw.wait(5)
        assert calls and calls[0][0].E > d.E

    def test_failed_compile_clears_ledger_for_retry(self, monkeypatch):
        """A background compile failure must never propagate AND must clear
        the warmed ledger so a later cycle can retry."""
        import kubernetes_tpu.sched.prewarm as pm

        def boom(*a, **k):
            raise RuntimeError("compile backend down")

        monkeypatch.setattr(pm, "abstract_cycle_args", boom)
        pw = BucketPrewarmer(threshold=0.8, min_axis=8)
        pw.observe(Dims().grown_for(N=16), n_nodes=13, n_existing=1)
        pw.wait(10)
        assert not pw._warmed  # failure → signature eligible for retry


class TestGrowthAcrossBucketBoundary:
    def test_cycles_keep_running_while_cluster_grows(self):
        """The VERDICT scenario: node count grows across a Dims bucket
        boundary while waves keep scheduling. The prewarmer must have been
        asked for the next bucket BEFORE the boundary was crossed, and
        every cycle must keep placing pods (no failed cycles, no stalls
        waiting on anything but the ordinary dispatch)."""
        from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

        calls = []

        binder = RecordingBinder()
        s = Scheduler(binder=binder, base_dims=Dims().grown_for(N=16, E=16))
        s.prewarmer = BucketPrewarmer(
            threshold=0.8, min_axis=8,
            compile_fn=lambda d, e, x, g, m=None, fleet=None:
            calls.append(d))

        for i in range(8):
            s.on_node_add(mknode(i))
        pod_i = 0

        def feed(k):
            nonlocal pod_i
            for _ in range(k):
                s.on_pod_add(Pod(name=f"p{pod_i}",
                                 requests=Resources.make(cpu="100m"),
                                 creation_index=pod_i))
                pod_i += 1

        # grow 8 → 24 nodes (crosses the N=16 bucket), scheduling each step
        for n in range(8, 24):
            s.on_node_add(mknode(n))
            feed(2)
            stats = s.schedule_pending()
            assert stats.scheduled == 2, f"stall at {n + 1} nodes"
        s.prewarmer.wait(5)
        assert calls, "prewarmer never fired while growing to the boundary"
        assert any(d.N > 16 for d in calls)
        assert len(binder.bound) == pod_i


class TestMeshSignatureIsolation:
    """ISSUE 3 satellite: executables are keyed on (bucket, mesh signature),
    so single-device and mesh programs never cross-pollinate — after a
    device loss → CPU fallback → re-admission cycle, no mesh-shaped
    executable can ever be handed single-device arrays (a silent reshard
    onto possibly-dead devices) and vice versa."""

    def _mesh(self):
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        from kubernetes_tpu.parallel.mesh import make_mesh

        return make_mesh(8)

    def test_mesh_and_single_device_warm_separate_keys(self):
        mesh = self._mesh()
        calls = []
        pw = BucketPrewarmer(
            threshold=0.8, min_axis=8,
            compile_fn=lambda d, e, x, g, m=None, fleet=None:
            calls.append((d, m)))
        d = Dims().grown_for(N=16, E=16)
        pw.observe(d, n_nodes=14, n_existing=1)              # single-device
        pw.wait(5)
        pw.observe(d, n_nodes=14, n_existing=1, mesh=mesh)   # mesh
        pw.wait(5)
        assert len(calls) == 2
        assert calls[0][1] is None and calls[1][1] is mesh

    def test_lookup_isolation_across_mesh_signatures(self):
        """A Compiled stored under the mesh key must be invisible to a
        single-device lookup at identical dims (and vice versa)."""
        mesh = self._mesh()
        pw = BucketPrewarmer(threshold=0.8, min_axis=8)
        d = Dims().grown_for(N=16, E=16)
        from dataclasses import replace

        from kubernetes_tpu.parallel.mesh import mesh_key

        base = replace(d, has_node_name=False)
        pw.compiled[(base, "waves", (), False, None,
                     mesh_key(mesh))] = "MESH-EXE"
        pw.compiled[(base, "waves", (), False, None, None)] = "SINGLE-EXE"
        assert pw.lookup(d, "waves", (), False, mesh=mesh) == "MESH-EXE"
        assert pw.lookup(d, "waves", (), False, mesh=None) == "SINGLE-EXE"
        # the engine is part of the key: the scan a nodeName batch is
        # routed to is another compiled program
        pw.compiled[(base, "scan", (), False, None, None)] = "SCAN-EXE"
        assert pw.lookup(d, "scan", (), False) == "SCAN-EXE"
        assert pw.lookup(d, "scan", (), True) is None
        # preempt programs carry the same isolation
        pw.compiled[pw._preempt_key(d, 8, mesh)] = "MESH-PREEMPT"
        assert pw.lookup_preempt(d, 8, mesh=None) is None
        assert pw.lookup_preempt(d, 8, mesh=mesh) == "MESH-PREEMPT"

    def test_mesh_abstract_args_carry_shardings(self):
        """abstract_cycle_args(mesh=...) must annotate the node tables with
        the node-axis sharding and everything else replicated — the AOT
        compile then produces the GSPMD executable the live path needs."""
        mesh = self._mesh()
        d = Dims().grown_for(N=16, P=16, E=16)
        tables, pending, keys, existing, hw, ecfg, _ = abstract_cycle_args(
            d, mesh=mesh)
        assert tables.nodes.alloc.sharding.spec == ("nodes",)
        assert tables.classes.rid.sharding.is_fully_replicated
        assert pending.cls.sharding.is_fully_replicated

    def test_mesh_abstract_args_compile_through_production_jit(self):
        """The sharded abstract pytree must AOT-compile through the
        production jit — the executable the rewarm path stores for the
        first post-recovery mesh wave."""
        mesh = self._mesh()
        from kubernetes_tpu.sched.cycle import _schedule_batch_impl

        d = Dims().grown_for(N=16, P=16, E=16)
        (tables, pending, keys, existing, hw, ecfg,
         gang) = abstract_cycle_args(d, mesh=mesh)
        compiled = _schedule_batch_impl.lower(
            tables, pending, keys, d.D, existing, "waves", hw, ecfg,
            (), (), gang).compile()
        assert compiled is not None

    def test_fleet_and_single_cluster_never_cross(self):
        """ISSUE 6: the tenant-stack signature is a key slot of its own — a
        K-tenant fleet Compiled is invisible to a single-cluster lookup at
        identical dims (and vice versa), across every K."""
        from dataclasses import replace

        pw = BucketPrewarmer(threshold=0.8, min_axis=8)
        d = Dims().grown_for(N=16, E=16)
        base = replace(d, has_node_name=False)
        pw.compiled[(base, "waves", (), False, 8, None)] = "FLEET-K8"
        pw.compiled[(base, "waves", (), False, None, None)] = "SINGLE"
        assert pw.lookup(d, "waves", (), False, fleet=8) == "FLEET-K8"
        assert pw.lookup(d, "waves", (), False) == "SINGLE"
        assert pw.lookup(d, "waves", (), False, fleet=16) is None
        # fleet × mesh compose: a tenant-axis-sharded fleet executable is
        # yet another key, invisible to both of the above
        mesh = self._mesh()
        from kubernetes_tpu.parallel.mesh import mesh_key

        pw.compiled[(base, "waves", (), False, 8,
                     mesh_key(mesh))] = "FLEET-K8-MESH"
        assert pw.lookup(d, "waves", (), False, fleet=8,
                         mesh=mesh) == "FLEET-K8-MESH"
        assert pw.lookup(d, "waves", (), False, fleet=8) == "FLEET-K8"

    def test_fleet_warm_compiles_the_stacked_program(self):
        """ensure_warm(fleet=K) must AOT-compile fleet/cycle.py's vmapped
        program from abstract shapes and store it under the fleet key —
        the executable the live fleet tick then calls directly."""
        d = Dims().grown_for(N=16, P=16, E=16)
        pw = BucketPrewarmer(threshold=0.8, min_axis=8)
        assert pw.ensure_warm(d, "waves", fleet=4)
        pw.wait(120)
        compiled = pw.lookup(d, "waves", (), False, fleet=4)
        assert compiled is not None
        # the single-cluster slot stays empty: nothing leaked across
        assert pw.lookup(d, "waves", (), False) is None
        # and the warm is idempotent per signature
        assert not pw.ensure_warm(d, "waves", fleet=4)

    @pytest.mark.chaos
    def test_loss_fallback_readmission_never_crosses_signatures(self):
        """The full drill: mesh serving → injected device error → degraded
        single-device wave → prober re-admission → reformed mesh. At every
        stage the prewarmer's stored executables must be keyed to the
        placement the NEXT dispatch will actually use: the loss invalidates
        everything (a mesh executable may be pinned to dead devices), and
        the re-admission rewarm targets the REFORMED mesh signature, never
        the dead one's."""
        import os

        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        from kubernetes_tpu.parallel.mesh import mesh_key
        from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler
        from kubernetes_tpu.utils import faultline

        os.environ["KTPU_PROBE_BACKOFF"] = "0.05"
        faultline.install("device.error@cycle:2,mesh.degrade@probe:1")
        try:
            s = Scheduler(binder=RecordingBinder(), mesh=8, batch_size=4,
                          base_dims=Dims().grown_for(N=16, P=4, E=64))
            lookups = []
            orig_lookup = s.prewarmer.lookup

            def spy_lookup(d, engine, extras, gang, mesh=None):
                lookups.append(mesh_key(mesh))
                return orig_lookup(d, engine, extras, gang, mesh=mesh)

            s.prewarmer.lookup = spy_lookup
            for i in range(8):
                s.on_node_add(mknode(i))
            for i in range(16):
                s.on_pod_add(Pod(name=f"p{i}",
                                 requests=Resources.make(cpu="100m"),
                                 creation_index=i))
            mesh0 = s.mesh_state.mesh
            assert mesh0 is not None
            s.schedule_pending()          # wave 1: healthy, mesh0
            s.schedule_pending()          # wave 2: injected loss → fallback
            assert s.supervisor.stats.degraded_cycles >= 1
            # the loss dropped the mesh AND every stored executable
            assert s.mesh_state.mesh is None or s.mesh_state.mesh is not mesh0
            assert not s.prewarmer.compiled
            assert s.supervisor.wait_recovered(timeout=30)
            mesh1 = s.mesh_state.mesh
            assert mesh1 is not None and mesh1 is not mesh0
            # the forced-degrade probe reformed NARROWER than the lost width
            assert len(mesh1.devices.flat) < len(mesh0.devices.flat)
            while s.queue.lengths()[0] > 0:
                s.schedule_pending()      # post-recovery waves on mesh1
            assert len(s.binder.bound) == 16
            # every lookup the dispatch path made was keyed to the mesh of
            # the snapshot it dispatched — degraded waves looked up the
            # single-device (None) signature, never a mesh one
            healthy_sigs = {None, mesh_key(mesh0), mesh_key(mesh1)}
            assert set(lookups) <= healthy_sigs
            # and nothing stored under the DEAD mesh's signature survives
            assert all(k[-1] != mesh_key(mesh0)
                       for k in s.prewarmer.compiled)
        finally:
            faultline.uninstall()
            os.environ.pop("KTPU_PROBE_BACKOFF", None)
