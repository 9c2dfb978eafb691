"""Tenant stacking: K per-tenant ClusterTables behind one leading axis.

The fleet's layout invariant is that every tenant's encoded cluster shares
ONE capacity shape — the fleet bucket — so a single vmap'd program serves
all of them. That bucket is the field-wise union of the tenants' Dims
(`fleet_dims`), fed back into every tenant's cache snapshot as `base_dims`:
`state/cache.py` seeds its capacity growth from the union, so when ANY
tenant grows an axis, every other tenant's next snapshot pads up to match.
Padding semantics are exactly the ones `parallel/mesh.py:pad_node_tables`
already proves for the node axis — unoccupied slots are inert rows
(valid=False, zero capacity, -1 ids) that no engine can admit a pod onto —
applied here by the encoder's own bucketed staging, one axis at a time.

`FleetStack` keeps the STACKED trees resident on device (optionally sharded
across a fleet mesh — 1-D: each chip owns whole tenants, no collectives;
2-D `(TENANT_AXIS, NODE_AXIS)`: each tenant's node planes additionally
split across a device row, with cross-row argmax/psum inserted by GSPMD
exactly as the single-cluster node mesh proves): a tenant whose snapshot object
changed since the last tick scatters its row through the SAME donated-patch
path the mesh-resident single-cluster snapshot uses
(`state/cache.py:_patch_resident`); unchanged tenants cost nothing, and the
mesh steady state (every tenant changed) takes one sharded full restack
instead of replicating the whole stack to every device as patch operands.
"""

from __future__ import annotations

from dataclasses import replace
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..state.dims import Dims

# floor on the padded tenant axis: K buckets to a multiple of the fleet
# mesh (or stays exact single-device), so the stacked shape signature is
# stable as tenants join
RC_TENANT_MIN = 1


def fleet_dims(tenant_dims: Sequence[Dims],
               base: Optional[Dims] = None) -> Dims:
    """The shared fleet bucket: field-wise union of every tenant's Dims
    (and the configured floor). `has_node_name` is cleared — it is a
    per-tick routing fact the server re-derives, not a capacity."""
    d = base or Dims()
    for td in tenant_dims:
        d = d.union(td)
    return replace(d, has_node_name=False)


def stack_blocks(blocks: Sequence[Tuple]):
    """Stack per-tenant pytrees (tables, pending, existing, (uk, ev)) into
    one tree with a leading tenant axis on every leaf."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def empty_tenant_block(d: Dims):
    """An inert PAD tenant: an empty cluster at the fleet bucket — every
    node row invalid, every pending/existing slot invalid, so it can never
    admit a pod (the tenant-axis analog of pad_node_tables' inert rows).
    Pads K up to the fleet mesh's divisibility requirement."""
    from ..state.arrays import ClusterTables
    from ..state.encode import Encoder

    enc = Encoder()
    tables = ClusterTables(
        nodes=enc.empty_node_arrays(d),
        reqs=enc.build_req_table(d),
        labelsets=enc.build_labelset_table(d),
        nterms=enc.build_nterm_table(d),
        tolsets=enc.build_tolset_table(d),
        portsets=enc.build_portset_table(d),
        terms=enc.build_term_table(d),
        classes=enc.build_class_table(d),
        images=enc.build_image_table(d),
        zone_keys=enc.build_zone_keys(),
        volsets=enc.build_volset_table(d),
        drv_masks=enc.build_drv_masks(d),
    )
    pending = enc.build_pod_arrays([], d, capacity=d.P)
    existing = enc.build_pod_arrays([], d, capacity=d.E)
    return (tables, pending, existing,
            (jnp.int32(0), jnp.int32(0)))


def abstract_fleet_args(d: Dims, K: int, mesh=None):
    """ShapeDtypeStruct pytrees for one `fleet/cycle.py:_fleet_cycle_impl`
    call: the single-cluster abstract args (sched/prewarm.py — shapes and
    pytree structure BY CONSTRUCTION the live ones) with a leading tenant
    axis of K prepended, plus the [K] quota vector and the shared traced
    scalars. With a tenant-axis `mesh`, every stacked leaf carries the
    fleet sharding (leading axis split) and the scalars replicate — the
    AOT compile produces the same GSPMD placement the live fleet path
    dispatches."""
    from ..ops.lattice import abstract_engine_config
    from ..sched.prewarm import abstract_cycle_args

    (tables, pending, keys, existing, _hw, _ecfg,
     _gang) = abstract_cycle_args(d)
    sh = rep = None
    tables_sh = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.mesh import fleet_sharding, fleet_shardings

        sh = fleet_sharding(mesh)
        rep = NamedSharding(mesh, PartitionSpec())
        # the stacked node planes shard (TENANT_AXIS, NODE_AXIS) on a 2-D
        # mesh; fleet_shardings is the SAME helper shard_fleet places
        # with, so AOT input shardings cannot drift from the live stack
        tables_sh = fleet_shardings(tables, mesh)

    stack = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((K,) + a.shape, a.dtype,
                                       sharding=sh), t)
    if tables_sh is not None:
        stack_tables = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct((K,) + a.shape, a.dtype,
                                              sharding=s),
            tables, tables_sh)
    else:
        stack_tables = stack(tables)
    vec = lambda dt: jax.ShapeDtypeStruct((K,), dt, sharding=sh)
    scalar_f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    return (stack_tables, stack(pending),
            (vec(jnp.int32), vec(jnp.int32)), stack(existing),
            vec(jnp.float32), scalar_f32,
            abstract_engine_config(rep))


class FleetStack:
    """The resident stacked fleet state and its per-tenant patch path.

    `refresh` compares each tenant's Snapshot by object identity (the cache
    returns the SAME object when nothing changed — generation, pending set,
    placement all equal), so idle tenants cost zero device work per tick;
    changed tenants scatter their row into the resident stacked tree via
    the donated patch path (`state/cache.py:_patch_resident` — XLA
    aliases the update in place, and the is_deleted assert proves it).
    Shape changes (the fleet bucket grew, a tenant joined) rebuild the
    whole stack — the fleet analog of the cache's full-snapshot path."""

    def __init__(self, mesh=None):
        # fleet jax Mesh (parallel/mesh.py): 1-D tenant axis, 2-D
        # tenant × node-shard, or None (single device)
        self.mesh = mesh
        self.block = None           # (tables, pending, existing, (uk, ev))
        self.dims: Optional[Dims] = None
        self.K = 0                  # padded leading dim (the stack's K)
        self.live = 0               # live (unpadded) tenant count
        self._snaps: List = []
        self._keys_host: List[Tuple[int, int]] = []
        # accounting mirrors the cache's resident-state counters; the
        # failure counter uses the cache's NAME so _patch_resident (the one
        # shared donation check, gated by KTPU_MESH_DONATION_STRICT for
        # fleet and single-cluster alike) can bump it duck-typed
        self.full_restacks = 0
        self.donated_patches = 0
        self.resident_donation_failures = 0

    @property
    def donation_failures(self) -> int:
        return self.resident_donation_failures

    def _put(self, tree):
        if self.mesh is not None:
            from ..parallel.mesh import shard_fleet

            return shard_fleet(tree, self.mesh)
        return jax.device_put(tree)

    def _put_rep(self, tree):
        """Patch operands (row indices + single-tenant rows) replicate
        across the fleet mesh; GSPMD routes the scatter to the owning
        shard."""
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            return jax.device_put(
                tree, NamedSharding(self.mesh, PartitionSpec()))
        return jax.device_put(tree)

    def invalidate(self) -> None:
        """Drop the resident stacked tree WITHOUT touching its buffers.
        Called when they may still be held by an abandoned dispatch's
        zombie worker, or live on a lost backend: donating (or even
        scattering onto) such buffers would corrupt an in-flight read or
        dispatch onto dead hardware — the next refresh full-restacks onto
        fresh buffers instead (the fleet analog of the cache's
        `_dispatch_inflight` copy gate and degraded-mode re-encode)."""
        self.block = None
        self.dims = None
        self._snaps = []
        self._keys_host = []

    def padded_k(self, live: int) -> int:
        """K pads to the TENANT-AXIS width of the mesh (not the flat device
        count — on a 2-D mesh each tenant row spans node-shard chips)."""
        if self.mesh is None:
            return max(live, RC_TENANT_MIN)
        from ..parallel.mesh import fleet_mesh_shape, padded_tenant_count

        kt, _ = fleet_mesh_shape(self.mesh)
        return padded_tenant_count(max(live, RC_TENANT_MIN), kt)

    def _node_shards(self) -> int:
        if self.mesh is None:
            return 1
        from ..parallel.mesh import fleet_mesh_shape

        return fleet_mesh_shape(self.mesh)[1]

    def _node_pad(self, block):
        """Pad the stacked tables' per-tenant node axis to the node-shard
        width (2-D mesh, directly-constructed shapes only — the server
        grows the fleet bucket so the serving path never pads here)."""
        kn = self._node_shards()
        if kn <= 1:
            return block
        from ..parallel.mesh import pad_fleet_node_tables

        return (pad_fleet_node_tables(block[0], kn),) + tuple(block[1:])

    def refresh(self, snaps: Sequence, keys: Sequence[Tuple], d: Dims):
        """Bring the resident stack current with this tick's per-tenant
        snapshots. Returns the padded tenant count K of the stacked tree."""
        live = len(snaps)
        Kp = self.padded_k(live)
        keys_host = [(int(uk), int(ev)) for uk, ev in keys]
        base = replace(d, has_node_name=False)
        kn = self._node_shards()
        # a bucket N that doesn't divide the node-shard row can't take the
        # shape-stable patch path (resident rows are node-padded, staging
        # rows are not) — restack with per-tenant inert node padding
        n_padded = kn > 1 and int(d.N) % kn != 0
        if (self.block is None or self.dims != base or self.K != Kp
                or self.live != live or n_padded):
            blocks = [(s.tables, s.pending, s.existing, k)
                      for s, k in zip(snaps, keys)]
            if Kp > live:
                pad = empty_tenant_block(d)
                blocks.extend([pad] * (Kp - live))
            self.block = self._put(self._node_pad(stack_blocks(blocks)))
            self.dims = base
            self.K = Kp
            self.live = live
            self.full_restacks += 1
        else:
            from ..state.cache import _patch_resident

            changed = [
                (k, snap, kh)
                for k, (snap, kh) in enumerate(zip(snaps, keys_host))
                if not (snap is self._snaps[k]
                        and kh == self._keys_host[k])]
            if (self.mesh is not None and changed
                    and len(changed) == live):
                # mesh steady state: EVERY tenant changed, so the patch
                # operands ARE the whole fleet state — and _put_rep
                # replicates them, uploading the full state once PER
                # DEVICE before the scatter. A sharded full restack
                # uploads it exactly once, split across the shards.
                blocks = [(s.tables, s.pending, s.existing, k)
                          for s, k in zip(snaps, keys)]
                if Kp > live:
                    blocks.extend([empty_tenant_block(d)] * (Kp - live))
                self.block = self._put(stack_blocks(blocks))
                self.full_restacks += 1
            elif changed:
                # ONE batched scatter for every changed tenant: in steady
                # state all K tenants pop a fresh batch each tick, and K
                # sequential single-row dispatches would put K host-device
                # round-trips on the hot path in front of the cycle.
                # The changed count is bucketed (cache._pad_patch: pad by
                # repeating the first entry — the repeated .set of
                # identical rows is idempotent) so the patch kernel
                # compiles once per power-of-two changed-tenant count, not
                # once per distinct count between 1 and K
                from ..state.cache import _pad_patch
                from ..state.dims import bucket as _bucket

                kb = _bucket(len(changed))
                padded = list(changed) + [changed[0]] * (kb - len(changed))
                rows = stack_blocks([
                    (snap.tables, snap.pending, snap.existing,
                     (jnp.int32(kh[0]), jnp.int32(kh[1])))
                    for _, snap, kh in padded])
                idx = self._put_rep(jnp.asarray(_pad_patch(
                    [k for k, _, _ in changed], kb), jnp.int32))
                rows = self._put_rep(rows)
                before = self.resident_donation_failures
                self.block = _patch_resident(self.block, idx, rows,
                                             donate=True, cache=self)
                if self.resident_donation_failures == before:
                    self.donated_patches += len(changed)
        self._snaps = list(snaps)
        self._keys_host = keys_host
        return self.K

    # convenience accessors for the dispatch layer
    @property
    def tables(self):
        return self.block[0]

    @property
    def pending(self):
        return self.block[1]

    @property
    def existing(self):
        return self.block[2]

    @property
    def keys(self):
        return self.block[3]
