"""Score-parity tensor kernels beyond the resource/affinity basics:

  * symmetric preferred inter-pod affinity weighting — the existing pods'
    PreferredDuringScheduling terms (and hard-affinity symmetric weight)
    pulling/pushing the incoming pod (interpod_affinity.go:119-215);
  * EvenPodsSpread SCORE for ScheduleAnyway constraints
    (priorities/even_pods_spread.go:106,139,175);
  * SelectorSpread — spread pods of the same Service/RC/RS/StatefulSet
    across hosts and zones (priorities/selector_spreading.go:58-165,
    zoneWeighting = 2/3);
  * ImageLocality — favor nodes already holding the pod's container images,
    spread-scaled against node heating (priorities/image_locality.go:39-92).

Everything here is expressed against the same interned TermTable/CNT carry
the predicates use, so the dynamic pieces stay live inside the assignment
loop (assume feedback) and the static pieces fold into the per-cycle lattice.
Pure-Python reference semantics: api/semantics.py (golden-tested).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..state.arrays import (
    Array,
    ClusterTables,
    NodeArrays,
    PodClassTable,
    TermTable,
)
from .interpod import domain_of_term
from .topospread import SpreadCounts, eligible_domain_counts

MAX_NODE_SCORE = 100.0

# hardPodAffinitySymmetricWeight default (apis/config/types.go:45-112 →
# DefaultHardPodAffinitySymmetricWeight = 1)
DEFAULT_HARD_POD_AFFINITY_WEIGHT = 1

# image size thresholds (image_locality.go:33-35), converted to KiB
IMG_MIN_KIB = 23 * 1024
IMG_MAX_KIB = 1000 * 1024

# selector_spreading.go:33 — zone score weight when zone info is present
ZONE_WEIGHTING = 2.0 / 3.0


def symmetric_weight_cols(
    classes: PodClassTable, S: int,
    hard_weight: int = DEFAULT_HARD_POD_AFFINITY_WEIGHT,
) -> Array:
    """WCOLS [S, SC] f32: the signed symmetric-preference weight an existing
    pod of class c contributes through term s to any incoming pod that term
    matches: +w for preferred affinity, −w for preferred anti-affinity,
    +hard_weight for REQUIRED affinity terms (interpod_affinity.go:156-185)."""
    SC = classes.valid.shape[0]
    out = jnp.zeros((S, SC), jnp.float32)

    def scatter(term_ids, w):  # [SC, A], [SC, A] → [S, SC]
        s = jnp.maximum(term_ids, 0)
        val = jnp.where(term_ids >= 0, w, 0).astype(jnp.float32)
        add = jnp.zeros((S + 1, SC), jnp.float32)
        add = add.at[
            jnp.where(term_ids >= 0, s, S).T, jnp.arange(SC)[None, :]
        ].add(val.T)
        return add[:S]

    out = out + scatter(classes.paff_terms, classes.paff_w)
    out = out - scatter(classes.panti_terms, classes.panti_w)
    hard = scatter(classes.aff_terms, jnp.ones_like(classes.aff_terms))
    out = out + hard * jnp.asarray(hard_weight, jnp.float32)
    return out * classes.valid[None, :]


def weighted_per_node(WCOLS: Array, M: Array) -> Array:
    """WSYM seed [S, N] f32: Σ over existing pods of their class's signed
    symmetric weights, per node — the cycle-start counterpart of
    processExistingPod (interpod_affinity.go:124-185) — as WCOLS [S, SC] ×
    M [SC, N] (interpod.class_node_hist). Precision.HIGHEST keeps every f32
    bit of both sides (the chip's default would round them to bf16): the
    weights are integer-valued and the counts below 2^16, so each sum is the
    exact integer a scatter-add over the pods would reach, in any order."""
    return jnp.dot(WCOLS, M.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def sym_affinity_contrib(cls: Array, TM: Array, sym: Array) -> Array:
    """[N] f32 raw symmetric contribution for one incoming pod: for every term
    s the pod MATCHES (TM [S, SC] at cls), credit every node sharing the
    topology domain of a contributing existing pod (processTerm's fixed-term
    spreading over same-topology nodes, interpod_affinity.go:87-117). `sym`
    [S, N] is WSYM, the live signed weights, summed over each node's domain
    of the term's key (interpod.in_domain_sums; 0 where the node lacks it).
    Added to the raw preferred-affinity counts BEFORE min-max
    normalization."""
    return jnp.where(TM[:, cls][:, None], sym, 0.0).sum(0)


def even_spread_soft_row(
    cls: Array,
    classes: PodClassTable,
    terms: TermTable,
    CNT: Array,            # [S, N] live counts
    nodes: NodeArrays,
    node_match_row: Array, # [N] this class's selector/affinity eligibility
    D: int,
    same: Array | None = None,    # CycleArrays.SAME
    counts: SpreadCounts | None = None,   # the state's, over every class
) -> Array:
    """[N] f32 0..100: EvenPodsSpread score over ScheduleAnyway constraints
    (even_pods_spread.go:106-227). Raw score per node = Σ matching pods in
    the node's topology domain (the count hard spread's Filter reads:
    topospread.eligible_domain_counts, or the class's rows of `counts`
    where the caller built the state's); normalized inverted
    (total−raw)/(total−min), ineligible nodes (missing key / failing node
    match) score 0.

    Deviation (docs/PARITY.md): normalization runs over all valid eligible
    nodes, not just the cycle's feasible set — ordering is unaffected."""
    s_ids = classes.tsc_term[cls]                 # [TS]
    s = jnp.maximum(s_ids, 0)
    soft = (s_ids >= 0) & ~classes.tsc_hard[cls]  # [TS]

    _, has_key = domain_of_term(nodes, terms.topo_key[s])  # [TS, N]
    cnt = counts.cnt[cls] if counts is not None else eligible_domain_counts(
        cls, classes, terms, CNT, node_match_row, nodes, D, same)
    raw = jnp.where(soft[:, None] & has_key, cnt, 0).sum(0)  # [N] i32

    elig = (
        node_match_row & nodes.valid
        & (~soft[:, None] | has_key).all(0)  # all soft keys present
    )
    any_soft = soft.any()
    rawf = raw.astype(jnp.float32)
    total = jnp.sum(jnp.where(elig, rawf, 0.0))
    mn = jnp.min(jnp.where(elig, rawf, jnp.inf))
    denom = total - jnp.where(jnp.isinf(mn), 0.0, mn)
    score = jnp.where(
        denom > 0,
        MAX_NODE_SCORE * (total - rawf) / jnp.maximum(denom, 1e-9),
        MAX_NODE_SCORE,
    )
    return jnp.where(any_soft & elig, score, 0.0)


def selector_spread_row(
    cls: Array,
    classes: PodClassTable,
    CNT: Array,          # [S, N]
    nodes: NodeArrays,
    zone_keys: Array,    # [2] i32 topo-key ids, -1 absent
    D: int,
) -> Array:
    """[N] f32 0..100: SelectorSpread (selector_spreading.go:62-165).
    count = matching pods of the pod's owning Services/controllers on each
    node; node score = 100·(maxCount−count)/maxCount, blended 1/3:2/3 with
    the same statistic aggregated by zone when zone labels exist."""
    s_ids = classes.ssel_terms[cls]              # [SS]
    s = jnp.maximum(s_ids, 0)
    active = (s_ids >= 0)[:, None]               # [SS, 1]
    cnt = jnp.where(active, CNT[s], 0).sum(0)    # [N] i32
    cntf = cnt.astype(jnp.float32)
    has_sel = (s_ids >= 0).any()

    valid = nodes.valid
    max_n = jnp.max(jnp.where(valid, cntf, 0.0))
    node_score = jnp.where(
        max_n > 0, MAX_NODE_SCORE * (max_n - cntf) / max_n, MAX_NODE_SCORE)

    # zone aggregation: modern zone label wins, legacy fills the gaps; the
    # two keys' compact domains live in disjoint halves of a 2D+1 bucket
    def zdom_of(kslot):
        k = zone_keys[kslot]
        col = nodes.domain[:, jnp.maximum(k, 0)]
        return jnp.where((k >= 0) & valid, col, -1)

    z0, z1 = zdom_of(0), zdom_of(1)
    zdom = jnp.where(z0 >= 0, z0, jnp.where(z1 >= 0, D + z1, -1))  # [N]
    has_zone = zdom >= 0
    idx = jnp.where(has_zone, zdom, 2 * D)
    zcounts = jnp.zeros((2 * D + 1,), jnp.float32).at[idx].add(
        jnp.where(has_zone, cntf, 0.0))
    zcnt = zcounts[idx]                                   # [N]
    max_z = jnp.max(zcounts[: 2 * D])
    zone_score = jnp.where(
        max_z > 0, MAX_NODE_SCORE * (max_z - zcnt) / max_z, MAX_NODE_SCORE)
    have_zones = has_zone.any()

    blended = jnp.where(
        have_zones & has_zone,
        node_score * (1.0 - ZONE_WEIGHTING) + ZONE_WEIGHTING * zone_score,
        node_score,
    )
    return jnp.where(has_sel & valid, blended, 0.0)


def image_locality_static(tables: ClusterTables) -> Array:
    """[SC, N] f32 0..100: ImageLocality (image_locality.go:39-92). Static per
    cycle — depends only on node image states. sumScore(c, n) =
    Σ_{img ∈ class} present(n, img)·size(img)·spread(img), spread =
    nodesWithImage/totalNodes; clamped to [23MiB, 1000MiB] then scaled."""
    nodes, classes, images = tables.nodes, tables.classes, tables.images
    N = nodes.valid.shape[0]
    img_ids = classes.img_ids                      # [SC, CI]
    safe = jnp.maximum(img_ids, 0)
    word = safe >> 5
    bit = (safe & 31).astype(jnp.uint32)
    words = nodes.img_words[:, word]               # [N, SC, CI]
    bits = ((words >> bit[None, :, :]) & 1).astype(jnp.int32)
    bits = bits * nodes.valid[:, None, None]       # [N, SC, CI]
    present = jnp.transpose(bits.astype(bool), (1, 2, 0)) \
        & (img_ids >= 0)[:, :, None]               # [SC, CI, N]

    total_nodes = jnp.maximum(nodes.valid.sum(), 1).astype(jnp.float32)
    # ImageStateSummary.NumNodes: how many nodes hold the image cluster-wide
    num_nodes = bits.sum(0) * (img_ids >= 0)       # [SC, CI]
    spread = num_nodes.astype(jnp.float32) / total_nodes
    size = images.size_kib[safe].astype(jnp.float32) * (img_ids >= 0)
    scaled = size * spread                          # [SC, CI]
    sums = (present * scaled[:, :, None]).sum(1)    # [SC, N]
    clamped = jnp.clip(sums, IMG_MIN_KIB, IMG_MAX_KIB)
    return (MAX_NODE_SCORE * (clamped - IMG_MIN_KIB)
            / float(IMG_MAX_KIB - IMG_MIN_KIB))
