"""Multi-chip sharding correctness: the node-axis-sharded cycle must produce
bit-identical results to the unsharded one.

The reference parallelizes Filter/Score with 16 goroutines over node chunks
(workqueue.ParallelizeUntil, core/generic_scheduler.go:537,770) and unit-tests
that path; here the chunking is a jax.sharding.Mesh over the node axis and the
collectives (argmax / any-reductions across chips) are inserted by XLA GSPMD
from the sharding annotations — this test is what makes that claim *tested*
rather than asserted (conftest forces 8 virtual CPU devices).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.models.workloads import flagship_pods, make_nodes
from kubernetes_tpu.ops.assign import assign_batch, feasible_matrix, initial_state
from kubernetes_tpu.ops.lattice import build_cycle
from kubernetes_tpu.ops.waves import assign_waves
from kubernetes_tpu.parallel.mesh import (
    MeshState, make_mesh, mesh_key, pad_node_tables, replicate, shard_tables)
from kubernetes_tpu.sched.cycle import UNSCHEDULABLE_TAINT_KEY
from kubernetes_tpu.state.dims import Dims
from kubernetes_tpu.state.encode import Encoder

ENGINES = {"scan": assign_batch, "waves": assign_waves}

# tier-1 runs these under JAX_PLATFORMS=cpu with 8 forced host devices
# (conftest.py); the skip guards environments where device forcing is
# unavailable (e.g. a pinned real-accelerator run with fewer chips)
pytestmark = [
    pytest.mark.mesh,
    pytest.mark.skipif(len(jax.devices()) < 8,
                       reason="needs 8 (virtual) devices — set XLA_FLAGS="
                              "--xla_force_host_platform_device_count=8"),
]


def _encode(n_nodes, n_pods, n_bound=0, groups=12):
    """`n_bound` further flagship pods sit on nodes already (several to a
    node, so the cycle's per-node seeds CNT / HOLD / WSYM are not zero)."""
    nodes = make_nodes(n_nodes, zones=min(8, n_nodes), racks_per_zone=4)
    pods = flagship_pods(n_pods + n_bound, groups=min(groups, n_pods))
    existing = pods[n_pods:]
    for i, p in enumerate(existing):
        p.node_name = nodes[(i * 7) % (n_nodes // 2)].name
    enc = Encoder()
    enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
    enc.vocabs.label_vals.intern("")
    tables, ex, pe, d = enc.encode_cluster(
        nodes, existing, pods[:n_pods], Dims(N=n_nodes, P=n_pods))
    uk = jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY))
    ev = jnp.int32(enc.vocabs.label_vals.get(""))
    return tables, pe, ex, uk, ev, d


def _cycle(tables, pending, existing, uk, ev, D, engine):
    cyc = build_cycle(tables, existing, uk, ev, D)
    init = initial_state(tables, cyc)
    res = ENGINES[engine](tables, cyc, pending, init)
    feas = feasible_matrix(tables, cyc, pending)
    return res.node, res.feasible, res.state.used, feas


@pytest.fixture(scope="module")
def cluster():
    return _encode(64, 96)


@pytest.fixture(scope="module")
def populated():
    return _encode(64, 96, n_bound=160)


@pytest.fixture(scope="module")
def eight_groups():
    """One deployment group a mesh device: 12 replicas a class."""
    return _encode(64, 96, groups=8)


def test_mesh_requires_enough_devices():
    with pytest.raises(RuntimeError, match="devices visible"):
        make_mesh(len(jax.devices()) + 1)


@pytest.mark.parametrize("engine", ["waves", "scan"])
@pytest.mark.parametrize("which", ["cluster", "populated", "eight_groups"])
def test_sharded_cycle_matches_unsharded(request, which, engine):
    """Both engines — `waves` (the production default) and `scan` (the
    executable spec) — must be bit-identical sharded vs unsharded, on an
    empty cluster (of 12 deployment groups, and of 8) and on one whose
    existing pods seed the per-node counts (the class × node histogram
    scatters into a node-sharded array and the seeds' products shard
    along N)."""
    tables, pending, existing, uk, ev, d = request.getfixturevalue(which)
    D = d.D
    # the in-domain sums are products against the same-domain matrices here
    # (state/dims.py domain_sum): they contract the sharded node axis
    assert d.domain_sum(engine) == "product"

    fn = jax.jit(lambda t, p, e, u, v: _cycle(t, p, e, u, v, D, engine))

    # unsharded (single-device) reference run
    ref_node, ref_feas, ref_used, ref_mat = jax.tree.map(
        np.asarray, fn(tables, pending, existing, uk, ev)
    )

    # sharded over the 8-virtual-device mesh: node tables split on N,
    # everything else replicated; GSPMD inserts the cross-chip reductions
    mesh = make_mesh(8)
    st = shard_tables(tables, mesh)
    sp = replicate(pending, mesh)
    se = replicate(existing, mesh)
    got_node, got_feas, got_used, got_mat = jax.tree.map(
        np.asarray, fn(st, sp, se, uk, ev)
    )

    assert int(got_feas.sum()) > 0, "sharded cycle scheduled nothing"
    np.testing.assert_array_equal(got_node, ref_node)
    np.testing.assert_array_equal(got_feas, ref_feas)
    np.testing.assert_array_equal(got_used, ref_used)
    np.testing.assert_array_equal(got_mat, ref_mat)


def test_sharded_seeds_match_unsharded(populated):
    """build_cycle's CNT, HOLD and WSYM, and the in-domain table built from
    them (interpod.term_domain_counts: cnt, hold, sym [S, N], tot [S]) as a
    product against the same-domain matrices (which contracts the SHARDED
    node axis: XLA closes it with a reduction across the mesh), under the
    node-sharded mesh, bit for bit those of one device, and the scatter
    form's — and not all zero."""
    from kubernetes_tpu.ops.interpod import term_domain_counts

    tables, _pending, existing, uk, ev, d = populated
    assert d.domain_sum("waves") == "product"

    @jax.jit
    def fn(t, e):
        cyc = build_cycle(t, e, uk, ev, d.D)
        assert cyc.SAME is not None
        table = term_domain_counts(t.terms, cyc.CNT, cyc.HOLD, cyc.WSYM,
                                   t.nodes, d.D, cyc.SAME)
        return cyc.CNT, cyc.HOLD, cyc.WSYM, *table

    @jax.jit
    def scatter(t, e):
        cyc = build_cycle(t, e, uk, ev, d.D)
        return tuple(term_domain_counts(t.terms, cyc.CNT, cyc.HOLD, cyc.WSYM,
                                        t.nodes, d.D))

    ref = jax.tree.map(np.asarray, fn(tables, existing))
    mesh = make_mesh(8)
    got = jax.tree.map(np.asarray, fn(shard_tables(tables, mesh),
                                      replicate(existing, mesh)))
    assert all(a.any() for a in ref)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(got[3:], jax.tree.map(np.asarray,
                                          scatter(tables, existing))):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_sharded_tables_placement(cluster):
    tables, *_ = cluster
    mesh = make_mesh(8)
    st = shard_tables(tables, mesh)
    # node rows live split across all 8 devices; class tables are replicated
    assert len(st.nodes.alloc.sharding.device_set) == 8
    assert not st.nodes.alloc.sharding.is_fully_replicated
    assert st.classes.rid.sharding.is_fully_replicated


def test_make_mesh_error_carries_xla_flags_note():
    """The raise on too-few devices must surface the virtual-mesh hint via
    PEP 678 __notes__ so wrapped/re-raised errors keep the fix visible."""
    with pytest.raises(RuntimeError) as ei:
        make_mesh(len(jax.devices()) + 1)
    notes = getattr(ei.value, "__notes__", [])
    assert any("xla_force_host_platform_device_count" in n for n in notes)


class TestNodeAxisPadding:
    """shard_tables on a node count that does NOT divide the mesh: the axis
    is padded with inert rows (zero capacity, invalid, unschedulable) and
    the padded run stays bit-equal to the unpadded single-device one with
    ZERO phantom admissions onto pad rows."""

    def _sliced(self, n_real):
        # build at a bucketed shape, then slice the node planes down to a
        # deliberately non-divisible row count — engines accept any N
        tables, pending, existing, uk, ev, d = _encode(64, 96)
        nodes = type(tables.nodes)(
            *[np.asarray(a)[:n_real] for a in tables.nodes])
        return tables._replace(nodes=nodes), pending, existing, uk, ev, d

    def test_pad_node_tables_shapes_and_inertness(self):
        tables, *_ = self._sliced(60)
        padded = pad_node_tables(tables, 8)
        assert padded.nodes.valid.shape[0] == 64
        assert not np.asarray(padded.nodes.valid[60:]).any()
        assert np.asarray(padded.nodes.unschedulable[60:]).all()
        assert (np.asarray(padded.nodes.alloc[60:]) == 0).all()
        assert (np.asarray(padded.nodes.name_id[60:]) == -1).all()
        # divisible counts are returned untouched
        assert pad_node_tables(padded, 8) is padded

    @pytest.mark.parametrize("n_real", [60, 57])
    def test_nondivisible_bit_equal_zero_phantoms(self, n_real):
        """Two contracts at once. (1) The sharded padded run is bit-equal to
        the SINGLE-DEVICE run at the same padded capacity — the serving
        comparison, where cache.snapshot pins d.N to the padded bucket for
        both placements (placements are a deterministic function of the
        capacity shape: the wave engine's tie-break rotation is keyed mod
        N, waves.py nextStartNodeIndex analog). (2) Padding itself is
        SEMANTICALLY inert vs the unpadded shape: identical feasibility,
        zero phantom admissions onto pad rows, untouched pad capacity."""
        tables, pending, existing, uk, ev, d = self._sliced(n_real)
        D = d.D

        fn = jax.jit(lambda t, p, e, u, v: _cycle(t, p, e, u, v, D, "waves"))
        raw_node, raw_feas, _, raw_mat = jax.tree.map(
            np.asarray, fn(tables, pending, existing, uk, ev))

        mesh = make_mesh(8)
        padded = pad_node_tables(tables, 8)
        st = shard_tables(tables, mesh)   # pads N → next multiple of 8
        Np = int(st.nodes.valid.shape[0])
        assert Np % 8 == 0 and Np > n_real
        assert padded.nodes.valid.shape[0] == Np
        sp = replicate(pending, mesh)
        se = replicate(existing, mesh)
        node, feas, used, mat = jax.tree.map(
            np.asarray, fn(st, sp, se, uk, ev))
        ref_node, ref_feas, ref_used, ref_mat = jax.tree.map(
            np.asarray, fn(padded, pending, existing, uk, ev))

        assert int(feas.sum()) > 0, "padded sharded cycle scheduled nothing"
        # (1) sharded == single-device at the same padded capacity, bit-equal
        np.testing.assert_array_equal(node, ref_node)
        np.testing.assert_array_equal(feas, ref_feas)
        np.testing.assert_array_equal(used, ref_used)
        np.testing.assert_array_equal(mat, ref_mat)
        # (2) padding is inert: zero phantom admissions on pad rows, pad
        # capacity untouched, feasibility identical to the unpadded shape
        assert (node < n_real).all()
        assert (used[n_real:] == 0).all()
        np.testing.assert_array_equal(feas, raw_feas)
        np.testing.assert_array_equal(mat[:, :n_real], raw_mat)
        assert not mat[:, n_real:].any()
        assert int(feas.sum()) == int(raw_feas.sum())
        del raw_node  # placements may legitimately differ across capacities


class TestMeshResidentCache:
    """The live serving path (ISSUE 3 tentpole): ClusterTables placed once
    via shard_tables, steady-state snapshots DONATE scatter updates into
    the resident sharded buffers, and the double-buffer keeps a prestage
    upload from ever donating in-flight arrays."""

    def _mk_sched(self, n_nodes=16, batch=8):
        from kubernetes_tpu.api.types import Node, Resources
        from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

        s = Scheduler(binder=RecordingBinder(), mesh=8, batch_size=batch,
                      base_dims=Dims().grown_for(N=n_nodes, P=batch, E=64))
        for i in range(n_nodes):
            s.on_node_add(Node(name=f"n{i}", allocatable=Resources.make(
                cpu="64", memory="64Gi", pods=110)))
        return s

    def _feed(self, s, k, start=0):
        from kubernetes_tpu.api.types import Pod, Resources

        for i in range(start, start + k):
            s.on_pod_add(Pod(name=f"p{i}",
                             requests=Resources.make(cpu="100m"),
                             creation_index=i))

    def test_snapshot_places_tables_sharded_and_rest_replicated(self):
        s = self._mk_sched()
        self._feed(s, 4)
        snap, _ = s._snapshot_keys(s.queue.peek_active(4))
        assert snap.mesh is s.mesh_state.mesh
        assert len(snap.tables.nodes.alloc.sharding.device_set) == 8
        assert not snap.tables.nodes.alloc.sharding.is_fully_replicated
        assert snap.tables.classes.rid.sharding.is_fully_replicated
        assert snap.pending.cls.sharding.is_fully_replicated
        assert snap.existing.cls.sharding.is_fully_replicated

    def test_steady_state_donates_never_reuploads(self):
        """The acceptance assert: after the one cold upload, every on-path
        snapshot patches the resident shards with DONATED buffers — no
        full-snapshot device_put on the steady-state path, and the donation
        check (is_deleted on the old buffers) ran without tripping."""
        s = self._mk_sched()
        self._feed(s, 40)
        while s.queue.lengths()[0] > 0:
            s.schedule_pending()
        assert len(s.binder.bound) == 40
        assert s.cache.resident_full_uploads == 1
        assert s.cache.resident_donated_patches >= 3
        # the prestage half of the double buffer ran while waves were in
        # flight and took the copy path (donating would have deleted
        # buffers the dispatch worker still held)
        assert s.cache.resident_copy_patches >= 1
        assert s.cache._dispatch_inflight == 0

    def test_mesh_placements_bit_equal_to_single_device(self):
        """End-to-end serving equality: the same cluster + pod stream via
        the mesh-resident path and the single-device path must bind every
        pod to the same node."""
        from kubernetes_tpu.api.types import Node, Pod, Resources
        from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

        def run(mesh):
            s = Scheduler(binder=RecordingBinder(), mesh=mesh, batch_size=8,
                          base_dims=Dims().grown_for(N=16, P=8, E=64))
            for i in range(16):
                s.on_node_add(Node(name=f"n{i}",
                                   allocatable=Resources.make(
                                       cpu="8", memory="16Gi", pods=110)))
            for i in range(40):
                s.on_pod_add(Pod(name=f"p{i}",
                                 requests=Resources.make(cpu="100m"),
                                 creation_index=i))
            while s.queue.lengths()[0] > 0:
                s.schedule_pending()
            return sorted(s.binder.bound)

        assert run(mesh=8) == run(mesh=None)

    @pytest.mark.chaos
    def test_device_loss_degrades_reshards_and_recovers(self, monkeypatch):
        """Tentpole part 3: losing a device of the mesh mid-run is a
        first-class fault — the wave degrades to the single-device CPU
        fallback (never touching mesh buffers via the resident patch
        path), the prober re-admits, the supervisor reforms a SMALLER mesh
        (the forced-degrade probe), resident state re-shards from host
        staging onto it, and not one pod is lost."""
        from kubernetes_tpu.utils import faultline

        monkeypatch.setenv("KTPU_PROBE_BACKOFF", "0.05")
        faultline.install(
            "device.error@cycle:2,mesh.degrade@probe:1")
        try:
            s = self._mk_sched()
            mesh0 = s.mesh_state.mesh
            self._feed(s, 48)
            waves = 0
            while s.queue.lengths()[0] > 0 and waves < 24:
                s.schedule_pending()
                waves += 1
                if not s.supervisor.healthy:
                    assert s.supervisor.wait_recovered(timeout=30)
            st = s.supervisor.stats
            assert st.degraded_cycles >= 1, "fault fired but nothing degraded"
            assert st.recoveries >= 1
            assert s.mesh_state.demotions == 1
            mesh1 = s.mesh_state.mesh
            assert mesh1 is not None
            assert len(mesh1.devices.flat) < len(mesh0.devices.flat)
            # post-reform resident state lives sharded on the NEW mesh
            snap = s.cache._snapshot
            assert snap.mesh is mesh1
            assert (len(snap.tables.nodes.alloc.sharding.device_set)
                    == len(mesh1.devices.flat))
            # crash consistency: every pod bound exactly once, none lost
            bound = [k for k, _ in s.binder.bound]
            assert len(bound) == 48 and len(set(bound)) == 48
            assert sum(s.queue.lengths()) == 0
        finally:
            faultline.uninstall()

    def test_mesh_state_reform_restores_full_width_when_probe_passes(self):
        ms = MeshState(8)
        assert ms.n_devices == 8
        ms.on_backend_loss()
        assert ms.mesh is None
        m_narrow = ms.reform()
        assert len(m_narrow.devices.flat) == 4   # half the lost width
        m_full = ms.reform(full=True)
        assert len(m_full.devices.flat) == 8
        # a later loss at full width halves again from the NEW width
        ms.on_backend_loss()
        assert len(ms.reform().devices.flat) == 4

    def test_mesh_key_distinguishes_widths_not_objects(self):
        m8a, m8b = make_mesh(8), make_mesh(8)
        assert mesh_key(m8a) == mesh_key(m8b)
        assert mesh_key(m8a) != mesh_key(make_mesh(4))
        assert mesh_key(None) is None
