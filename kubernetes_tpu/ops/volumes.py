"""Attachable-volume predicates as bitset ops: NoDiskConflict + the
max-volume-count family.

Reference semantics:
  * NoDiskConflict (predicates.go:156-221): two mounts of the same volume on
    one NODE conflict unless both are read-only (EBS-style always-conflict
    volumes are modeled read_only=False by the API layer);
  * MaxPDVolumeCount / CSIMaxVolumeLimit (predicates.go:223-…,
    csi_volume_predicate.go:89-160): DISTINCT attachable volumes per driver on
    a node must stay within the node's per-driver limit (CSINode allocatable /
    cloud caps; Node.volume_limits here, -1 = unlimited).

TPU design: a volume is in the vocab only once two pods name it (SHARED:
state/encode.py Encoder.vol_owner). For those the live per-node state is two
u32 bitsets over the vocab — vol_any (attached) and vol_rw (attached
read-write) — carried in the assignment state exactly like the host-port
words; their per-driver occupancy is DERIVED by popcount against static
driver masks, and same-wave commits compose with a bitwise-OR scan. A volume
only one pod names cannot conflict with anything and cannot be counted twice:
it is a count per driver, the class's `vol_priv` [DR] added to the node's
`vol_cnt` [N, DR] like a resource request, and same-wave commits compose by a
sum. So 2,000 pods with a claim each are one class, and no capacity follows
the number of such volumes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..state.arrays import Array, ClusterTables

def volume_components_row(
    tables: ClusterTables,
    vol_any: Array,   # [N, VW] live attached bitset
    vol_rw: Array,    # [N, VW] live read-write bitset
    vol_cnt: Array,   # [N, DR] live count of volumes of one pod alone
    cls: Array,       # scalar class id
) -> tuple[Array, Array]:
    """([N] conflict_free, [N] limit_ok) for one pod class against the live
    node volume state — the two predicates stay separable so the
    VolumeRestrictions and NodeVolumeLimits plugins can be toggled
    independently."""
    nodes = tables.nodes
    vs = tables.classes.volset[cls]
    safe = jnp.maximum(vs, 0)
    # a class with volumes of its own alone and no shared set has no words
    mine_any = jnp.where(vs >= 0, tables.volsets.any_words[safe], 0)  # [VW]
    mine_rw = jnp.where(vs >= 0, tables.volsets.rw_words[safe], 0)
    priv = tables.classes.vol_priv[cls]          # [DR]
    absent = (vs < 0) & ~(priv > 0).any()

    conflict = (
        ((mine_any[None, :] & vol_rw) != 0).any(-1)
        | ((mine_rw[None, :] & vol_any) != 0).any(-1)
    )

    after = vol_any | mine_any[None, :]                       # [N, VW]
    cnt = jax.lax.population_count(
        after[:, None, :] & tables.drv_masks[None, :, :]
    ).sum(-1).astype(jnp.int32) + vol_cnt + priv[None, :]     # [N, DR]
    lim = nodes.vol_limit                                      # [N, DR]
    limit_ok = ((lim < 0) | (cnt <= lim)).all(-1)

    return absent | ~conflict, absent | limit_ok


def volume_ok_row(tables, vol_any, vol_rw, vol_cnt, cls) -> Array:
    """[N] bool: both volume predicates (golden-test / component surface)."""
    c, l = volume_components_row(tables, vol_any, vol_rw, vol_cnt, cls)
    return c & l
