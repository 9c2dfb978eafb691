"""Multi-chip sharding of the scheduling lattice.

The reference scales Filter/Score with 16 goroutines on one box
(workqueue.ParallelizeUntil, generic_scheduler.go:537,770) and has no multi-
machine compute path at all — the control plane shards by *resource type*, not
by data. The TPU-native design shards the **node axis** across chips with a
`jax.sharding.Mesh`:

  * NodeArrays rows, the static [SC, N] lattice, per-node count carries
    (CNT/HOLD [S, N]) and the scan's [N]-wide dynamic rows are all partitioned
    on N — each chip owns N/n_devices nodes, exactly like the reference's
    goroutine chunking but over ICI instead of shared memory;
  * class/term tables are small and replicated;
  * the per-step argmax over N and `mask.any()` become cross-chip reductions —
    XLA GSPMD inserts the collectives (psum/all-gather over ICI) from the
    sharding annotations alone; no hand-written communication.

Pod-axis (batch) sharding — the long-context analog — composes on top for the
class-level matrices when SC×N outgrows one chip's HBM; the scan itself stays
sequential in pods by design (assume semantics).

Serving integration (the live path, not just the dryrun): `MeshState` owns
the mesh the scheduler dispatches on — `state/cache.py` keeps the encoded
`ClusterTables` RESIDENT on it (node axis split, patched with donated
scatters), `sched/prewarm.py` keys executables on the mesh signature, and
`sched/supervisor.py` drops/reforms the mesh across backend loss.
"""

from __future__ import annotations

import threading
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..state.arrays import ClusterTables, NodeArrays

NODE_AXIS = "nodes"

# the FLEET axis (fleet/ subsystem): K virtual tenant clusters stacked on a
# leading axis and split across chips. On a 1-D fleet mesh each chip owns
# K/n_devices whole tenants, so the vmap'd fleet cycle needs NO cross-chip
# collectives at all (tenants are independent by construction). The 2-D
# fleet mesh (TENANT_AXIS, NODE_AXIS) additionally splits each tenant's
# node tables across a device row — one huge tenant spreads over NODE_AXIS
# instead of capping the fleet — and the per-step argmax/psum become
# row-local collectives, exactly the reductions the single-cluster
# node-axis path already proves.
TENANT_AXIS = "tenants"

XLA_MESH_HINT = (
    "set XLA_FLAGS=--xla_force_host_platform_device_count=<n> and "
    "JAX_PLATFORMS=cpu for a virtual mesh"
)


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        err = RuntimeError(
            f"make_mesh({n}): only {len(devs)} devices visible — a multichip "
            "proof run on fewer devices than requested would validate nothing"
        )
        # PEP 678 notes: the actionable hint rides on the exception even
        # through re-raise/wrapping layers (3.10 tracebacks don't print
        # __notes__, so the hint is also queryable: err.__notes__)
        err.__notes__ = [XLA_MESH_HINT]
        raise err
    return Mesh(np.array(devs[:n]), (NODE_AXIS,))


def mesh_key(mesh: Optional[Mesh]) -> Optional[Tuple]:
    """Hashable signature of a mesh for executable/budget keying: shape and
    the concrete device ids. Two meshes with the same shape over DIFFERENT
    devices (pre- vs post-reform) must not share compiled programs — the old
    executable is pinned to the lost devices."""
    if mesh is None:
        return None
    return (mesh.devices.shape,
            tuple(d.id for d in mesh.devices.flat))


def padded_node_count(n: int, n_devices: int) -> int:
    """Smallest multiple of n_devices ≥ n."""
    return ((n + n_devices - 1) // n_devices) * n_devices


def _pad_node_arrays(nodes: NodeArrays, pad: int, axis: int = 0) -> NodeArrays:
    """Concatenate `pad` inert node rows along `axis` — the one fill rule
    both the single-cluster path (axis 0, the N axis) and the stacked fleet
    path (axis 1, the per-tenant N axis inside [K, N, …]) share. Id planes
    (int32) pad with -1 (absent — the empty_node_arrays convention);
    count/usage planes with 0; `unschedulable` with True; everything else
    with its dtype's zero. Every consumer is already gated on
    `nodes.valid`, so an inert row can never admit a pod."""

    def _concat(a, fill_value):
        a = np.asarray(a)
        shape = list(a.shape)
        shape[axis] = pad
        return np.concatenate(
            [a, np.full(shape, fill_value, a.dtype)], axis=axis)

    def _auto(a):
        arr = np.asarray(a)
        return _concat(arr, -1 if arr.dtype == np.int32 else 0)

    return NodeArrays(
        valid=_auto(nodes.valid),
        name_id=_auto(nodes.name_id),
        alloc=_concat(nodes.alloc, 0),
        used=_concat(nodes.used, 0),
        label_keys=_auto(nodes.label_keys),
        label_vals=_auto(nodes.label_vals),
        label_ints=_concat(nodes.label_ints, 0),
        unschedulable=_concat(nodes.unschedulable, True),
        taint_keys=_auto(nodes.taint_keys),
        taint_vals=_auto(nodes.taint_vals),
        taint_effects=_auto(nodes.taint_effects),
        topo=_auto(nodes.topo),
        domain=_auto(nodes.domain),
        port_pair_any=_auto(nodes.port_pair_any),
        port_pair_wild=_auto(nodes.port_pair_wild),
        port_triple=_auto(nodes.port_triple),
        img_words=_auto(nodes.img_words),
        vol_any=_auto(nodes.vol_any),
        vol_rw=_auto(nodes.vol_rw),
        vol_limit=_auto(nodes.vol_limit),
        vol_cnt=_auto(nodes.vol_cnt),
        avoid=_concat(nodes.avoid, False),
    )


def pad_node_tables(tables: ClusterTables, n_devices: int) -> ClusterTables:
    """Pad the node axis with inert rows (valid=False, zero capacity, every
    id -1 — the same fill as Encoder.empty_node_arrays' unoccupied slots) so
    N divides the mesh evenly. Inert rows are masked by `nodes.valid`
    everywhere the engines look, so they can never admit a pod; the padding
    test (tests/test_mesh.py) holds that to zero phantom admissions."""
    N = int(tables.nodes.valid.shape[0])
    Np = padded_node_count(N, n_devices)
    if Np == N:
        return tables
    return tables._replace(
        nodes=_pad_node_arrays(tables.nodes, Np - N, axis=0))


def _node_sharded_tables_spec(tables: ClusterTables) -> ClusterTables:
    """PartitionSpecs: NodeArrays sharded on axis 0 (the N axis); everything
    else replicated."""
    node_specs = type(tables.nodes)(
        *[P(NODE_AXIS) for _ in tables.nodes]
    )
    rep = lambda t: type(t)(*[P() for _ in t])
    return ClusterTables(
        nodes=node_specs,
        reqs=rep(tables.reqs),
        labelsets=rep(tables.labelsets),
        nterms=rep(tables.nterms),
        tolsets=rep(tables.tolsets),
        portsets=rep(tables.portsets),
        terms=rep(tables.terms),
        classes=rep(tables.classes),
        images=rep(tables.images),
        zone_keys=P(),
        volsets=rep(tables.volsets),
        drv_masks=P(),
    )


def table_shardings(tables: ClusterTables, mesh: Mesh) -> ClusterTables:
    """NamedSharding pytree matching `shard_tables`' placement — shared by
    the live placement path (state/cache.py) and the AOT prewarm path
    (sched/prewarm.py builds ShapeDtypeStructs carrying these)."""
    specs = _node_sharded_tables_spec(tables)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def shard_tables(tables: ClusterTables, mesh: Mesh) -> ClusterTables:
    """Place tables on the mesh: node axis split across chips, rest
    replicated. When dims.N does not divide the mesh evenly, the node axis is
    padded with inert rows first (zero capacity, invalid, unschedulable) —
    bucketed capacities make the divisible case the common one, but a raw
    Dims(N=...) from a caller must not crash the mesh path."""
    nd = len(mesh.devices.flat)
    tables = pad_node_tables(tables, nd)
    specs = _node_sharded_tables_spec(tables)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tables, specs
    )


def replicate(tree, mesh: Mesh):
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), tree
    )


# ---------------------------------------------------------------------- #
# fleet (tenant × node-shard) sharding — fleet/tables.py stacks K tenant
# clusters on a leading axis; these helpers split that axis across the
# mesh, and (2-D mesh) additionally split each tenant's node tables
# across a device row
# ---------------------------------------------------------------------- #


def make_fleet_mesh(n_devices: Optional[int] = None,
                    node_shards: int = 1) -> Mesh:
    """The fleet mesh. `node_shards=1` (default) is the legacy 1-D mesh
    over the TENANT axis — each chip owns whole tenants, no collectives.
    `node_shards=kn > 1` reshapes the same devices into a 2-D
    `(TENANT_AXIS, NODE_AXIS)` mesh of shape (n/kn, kn): each tenant's node
    tables split across a kn-wide device row, so one huge tenant spreads
    over the row instead of capping the fleet. Same device discipline as
    `make_mesh` (raises with the virtual-mesh hint when short); distinct
    axis names keep fleet and single-cluster programs from ever sharing
    sharding annotations by accident."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        err = RuntimeError(
            f"make_fleet_mesh({n}): only {len(devs)} devices visible")
        err.__notes__ = [XLA_MESH_HINT]
        raise err
    kn = int(node_shards or 1)
    if kn <= 1:
        return Mesh(np.array(devs[:n]), (TENANT_AXIS,))
    if kn > n or n % kn:
        raise ValueError(
            f"make_fleet_mesh({n}, node_shards={kn}): node_shards must "
            "divide the device count — the 2-D mesh is a (tenants, "
            "node-shards) reshape of the same devices")
    return Mesh(np.array(devs[:n]).reshape(n // kn, kn),
                (TENANT_AXIS, NODE_AXIS))


def fleet_mesh_shape(mesh: Mesh) -> Tuple[int, int]:
    """(tenant-axis width, node-shard width) of a fleet mesh. A legacy 1-D
    tenant mesh reads as (n, 1); the tenant width — NOT the flat device
    count — is what K pads up to (FleetStack.padded_k)."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    kt = shape.get(TENANT_AXIS, len(mesh.devices.flat))
    return int(kt), int(shape.get(NODE_AXIS, 1))


def padded_tenant_count(k: int, n_devices: int) -> int:
    """Smallest multiple of n_devices ≥ k — inert (empty-cluster) tenant
    slots pad the difference, exactly the `pad_node_tables` inert-row
    contract lifted one axis up."""
    return padded_node_count(k, n_devices)


def pad_fleet_node_tables(tables: ClusterTables,
                          node_shards: int) -> ClusterTables:
    """Pad a STACKED `[K, N, …]` ClusterTables tree so each tenant's node
    axis (axis 1) divides `node_shards` evenly — the `pad_node_tables`
    inert-row contract applied per tenant inside the stacked tree. The
    serving path never needs this (FleetServer grows the fleet bucket's N
    to a node-shard multiple before encoding), but a directly-constructed
    stack must not crash the 2-D mesh path."""
    N = int(tables.nodes.valid.shape[1])
    Np = padded_node_count(N, node_shards)
    if Np == N:
        return tables
    return tables._replace(
        nodes=_pad_node_arrays(tables.nodes, Np - N, axis=1))


def fleet_sharding(mesh: Mesh) -> NamedSharding:
    """The base NamedSharding of the fleet layout: a stacked leaf splits
    its leading (tenant) axis; later axes stay unsharded (on a 2-D mesh
    that means replicated across the node-shard row). Node planes of the
    stacked ClusterTables get the 2-D spec instead — see `fleet_specs`."""
    return NamedSharding(mesh, P(TENANT_AXIS))


def fleet_specs(tree, mesh: Mesh):
    """PartitionSpec pytree for a stacked fleet tree (every leaf [K, …]).
    Mirrors `_node_sharded_tables_spec` one axis up: on a 2-D mesh the
    stacked NodeArrays planes ([K, N, …]) shard (TENANT_AXIS, NODE_AXIS) —
    each tenant's nodes split across its device row — while every other
    leaf (class/term/req tables, pending/existing pods, keys, quotas)
    shards the tenant axis only, i.e. replicates across the row, because
    the per-step argmax over N reads every pod row on every row chip.
    On a 1-D mesh this degenerates to P(TENANT_AXIS) everywhere."""
    _, kn = fleet_mesh_shape(mesh)
    node_p = P(TENANT_AXIS, NODE_AXIS) if kn > 1 else P(TENANT_AXIS)
    tenant_p = P(TENANT_AXIS)

    def _specs(sub):
        if isinstance(sub, ClusterTables):
            return ClusterTables(
                nodes=type(sub.nodes)(*[node_p for _ in sub.nodes]),
                reqs=type(sub.reqs)(*[tenant_p for _ in sub.reqs]),
                labelsets=type(sub.labelsets)(
                    *[tenant_p for _ in sub.labelsets]),
                nterms=type(sub.nterms)(*[tenant_p for _ in sub.nterms]),
                tolsets=type(sub.tolsets)(*[tenant_p for _ in sub.tolsets]),
                portsets=type(sub.portsets)(
                    *[tenant_p for _ in sub.portsets]),
                terms=type(sub.terms)(*[tenant_p for _ in sub.terms]),
                classes=type(sub.classes)(*[tenant_p for _ in sub.classes]),
                images=type(sub.images)(*[tenant_p for _ in sub.images]),
                zone_keys=tenant_p,
                volsets=type(sub.volsets)(*[tenant_p for _ in sub.volsets]),
                drv_masks=tenant_p,
            )
        return jax.tree.map(lambda _: tenant_p, sub)

    return jax.tree.map(_specs, tree,
                        is_leaf=lambda x: isinstance(x, ClusterTables))


def fleet_shardings(tree, mesh: Mesh):
    """NamedSharding pytree matching `shard_fleet`'s placement — shared by
    the live placement path (fleet/tables.py FleetStack) and the AOT
    prewarm path (abstract_fleet_args), so compiled input shardings can
    never drift from what the server actually places."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        fleet_specs(tree, mesh),
                        is_leaf=lambda x: isinstance(x, P))


def shard_fleet(tree, mesh: Mesh):
    """Place a stacked fleet pytree (every leaf [K, …]) on the mesh: tenant
    axis split, and on a 2-D mesh each tenant's node planes additionally
    split across the node-shard row. K must already be a multiple of the
    tenant-axis width — the fleet stack pads with inert tenants first
    (fleet/tables.py) — and stacked node axes must divide the node-shard
    width (`pad_fleet_node_tables` when constructed directly)."""
    return jax.tree.map(jax.device_put, tree, fleet_shardings(tree, mesh))


class MeshState:
    """The serving scheduler's mesh lifecycle (sched/supervisor.py owns the
    health transitions):

      * `mesh` — the live mesh the next snapshot/dispatch should use, or
        None (single-device serving, exactly the pre-mesh behavior).
      * `on_backend_loss()` — a device of the mesh died (XlaRuntimeError,
        watchdog timeout): the WHOLE mesh is untrusted (GSPMD collectives
        span every chip), so serving drops to the supervisor's single-device
        CPU fallback immediately. The lost width is remembered.
      * `reform()` — re-admission: rebuild a mesh from the devices that are
        live NOW. After a loss the reformed mesh is SMALLER (largest power of
        two strictly below the lost width — the failed chip cannot be
        re-trusted blindly) unless the prober proved full width, in which
        case `reform(full=True)` restores it. A fresh Mesh object is built
        either way: state/cache.py keys residency on mesh identity, so
        reform forces the re-shard-from-host-staging path by construction.

    Device counts stay powers of two so the bucketed node axis (state/dims.py
    grown_for keeps N pow2-friendly) divides evenly without padding in the
    steady state; `shard_tables` pads when a raw shape doesn't.

    Fleet mode (`fleet_node_shards` not None): meshes are built with
    `make_fleet_mesh` instead — 1-D tenant mesh when node_shards is 1, the
    2-D (TENANT_AXIS, NODE_AXIS) mesh otherwise — so degrade/reform under
    the 2-D signature rides the exact same ladder: a loss drops the whole
    mesh, reform rebuilds (narrower after an unproven loss) with the
    node-shard width clamped to the reformed device count. Both widths are
    powers of two, so the clamp always divides."""

    def __init__(self, n_devices: Optional[int] = None,
                 fleet_node_shards: Optional[int] = None):
        self._mu = threading.Lock()
        self._requested = n_devices
        self._lost_width: Optional[int] = None
        self._fleet_ns = fleet_node_shards
        self.reforms = 0
        self.demotions = 0
        m = None
        avail = len(jax.devices())
        want = n_devices or avail
        if want > 1 and avail >= 2:
            m = self._build(self._pow2_floor(min(want, avail)))
        self.mesh: Optional[Mesh] = m

    def _build(self, width: int) -> Mesh:
        if self._fleet_ns is None:
            return make_mesh(width)
        ns = self._pow2_floor(max(int(self._fleet_ns), 1))
        return make_fleet_mesh(width, node_shards=min(ns, width))

    @staticmethod
    def _pow2_floor(n: int) -> int:
        return 1 << (max(n, 1).bit_length() - 1)

    @property
    def n_devices(self) -> int:
        with self._mu:
            return len(self.mesh.devices.flat) if self.mesh is not None else 1

    def on_backend_loss(self) -> None:
        """A mesh device is gone: drop the mesh entirely (collectives span
        all chips — there is no partial trust) and remember the width so
        reform comes back narrower."""
        with self._mu:
            if self.mesh is None:
                return
            self._lost_width = len(self.mesh.devices.flat)
            self.mesh = None
            self.demotions += 1

    def reform(self, full: bool = False) -> Optional[Mesh]:
        """Rebuild the mesh on re-admission. `full=True` (the prober proved
        every device answers) restores the requested width; otherwise the
        reformed mesh halves the lost width — losing one device of an 8-way
        mesh serves on 4 until a full-width probe passes."""
        with self._mu:
            avail = len(jax.devices())
            want = self._requested or avail
            if not full and self._lost_width is not None:
                want = min(want, max(self._lost_width // 2, 1))
            want = self._pow2_floor(min(want, avail))
            if want <= 1:
                self.mesh = None
                return None
            self.mesh = self._build(want)
            if full:
                self._lost_width = None
            self.reforms += 1
            return self.mesh
