"""Static (class × node) lattice: everything that does not change as pods land.

The reference evaluates ALL predicates per (pod, node) inside the scheduling
loop (generic_scheduler.go:473-537). On TPU we split Filter/Score into:

  * static parts — nodeSelector, node affinity (required + preferred), taints/
    tolerations, spec.unschedulable — which depend only on (pod-class, node) and
    are evaluated ONCE per cycle here, as [SC, N] tensors;
  * dynamic parts — resources, host ports, inter-pod affinity counts, topology
    spread counts — which depend on what landed earlier in the cycle and are
    re-evaluated as O(N) rows inside the assignment scan (ops/assign.py), the
    faithful analog of the reference's sequential assume semantics
    (scheduler.go:676 → cache.go:283).
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..state.arrays import Array, ClusterTables, PodArrays
from ..state.dims import domain_sum
from .interpod import (class_node_hist, class_term_membership, per_node_counts,
                       same_domain, term_class_matrix)
from .labels import node_term_matrix
from .scores import image_locality_static, symmetric_weight_cols, weighted_per_node
from .taints import taint_matrices, taint_toleration_score
from .topospread import eligible_in_domain


#: shape points / resource slots an EngineConfig carries for
#: RequestedToCapacityRatio (a Policy with more is refused: sched/config.py)
RTC_POINTS = 8
RTC_SLOTS = 16


class EngineConfig(NamedTuple):
    """How KubeSchedulerConfiguration's plugin composition reaches the fused
    one-dispatch engines: per-component filter enables and score weights as
    TRACED f32 scalars — config changes never recompile, a disabled plugin is
    flag/weight 0. Components correspond 1:1 to the in-tree plugin names
    (framework/plugins.py); plugins outside this fixed set (NodeLabel,
    NodePreferAvoidPods, …) run through the Framework plugin path.
    RequestedToCapacityRatio's arguments ride as traced ARRAYS of fixed
    length (`rtc_*`): two Policies that differ in the shape or the weights
    run one executable.

    The reference analog is the plugin set built by CreateFromConfig/
    CreateFromKeys (factory.go:309,387) driving which predicates/priorities
    run inside the scheduling loop."""

    f_unsched: Array        # NodeUnschedulable
    f_name: Array           # NodeName (spec.nodeName)
    f_ports: Array          # NodePorts
    f_node_affinity: Array  # NodeAffinity (nodeSelector + required affinity)
    f_fit: Array            # NodeResourcesFit
    f_taints: Array         # TaintToleration
    f_interpod: Array       # InterPodAffinity (required + symmetry)
    f_spread: Array         # PodTopologySpread (DoNotSchedule)
    f_volrestrict: Array    # VolumeRestrictions (NoDiskConflict)
    f_vollimits: Array      # NodeVolumeLimits (max attach counts)
    w_node_affinity: Array  # NodeAffinityScore (preferred terms)
    w_taint: Array          # TaintToleration score
    w_img: Array            # ImageLocality
    w_least: Array          # NodeResourcesLeastAllocated
    w_balanced: Array       # NodeResourcesBalancedAllocation
    w_most: Array           # NodeResourcesMostAllocated (0 in defaults)
    w_interpod: Array       # InterPodAffinity soft score (both directions)
    w_even: Array           # PodTopologySpread ScheduleAnyway score
    w_ssel: Array           # SelectorSpread
    # wave-admission score window (ops/waves.py): a class admits this wave
    # only on nodes scoring within `w_window` of its per-class feasible
    # max. MaxNodeScore=100 (interface.go:87) — one plugin's full swing —
    # keeps near-tied spreading parallel while a decisively-scored
    # preference (NodePreferAvoidPods' 0-vs-100 at configured weight,
    # strong preferred affinity) is honored instead of steamrolled by
    # same-wave intra-class spreading. The best node always qualifies, so
    # feasibility is untouched; tied clusters are unaffected.
    w_window: Array = 100.0
    # RequestedToCapacityRatio (ops/fit.py rtc_score_row): the priority's
    # weight; the shape's points, utilization ascending and scores on the
    # 0..100 scale, padded to RTC_POINTS by repeating the last; the weight
    # of every resource slot of the R axis (state/vocab.py resources, the
    # four fixed slots first), 0 = not in the map, padded to RTC_SLOTS
    w_rtc: Array = 0.0
    rtc_x: Array = np.zeros((RTC_POINTS,), np.float32)
    rtc_y: Array = np.zeros((RTC_POINTS,), np.float32)
    rtc_w: Array = np.zeros((RTC_SLOTS,), np.float32)


def _strong_f32(x):
    # python scalars become NUMPY f32 scalars: concrete (safe to build and
    # cache even while a jit trace is active — jnp.asarray there would
    # stage a traced constant and leak the tracer via the cache) and
    # strong-typed for jit. Already-normalized np.float32 leaves pass
    # through untouched so re-normalizing a config on the per-dispatch hot
    # path is free; other array leaves go through jnp.asarray.
    if isinstance(x, np.float32) or (
            isinstance(x, np.ndarray) and x.dtype == np.float32):
        return x
    if isinstance(x, (bool, int, float)):
        return np.float32(x)
    if isinstance(x, np.ndarray):
        return x.astype(np.float32)
    return jnp.asarray(x, jnp.float32)


def strong_engine_config(cfg: "EngineConfig") -> "EngineConfig":
    """Normalize an EngineConfig's leaves to STRONG-typed f32 scalars.
    Python floats trace as weak-typed f32, which keys a different jit cache
    entry (and a different persistent-cache HLO hash) than the prewarmer's
    strongly-typed abstract scalars — the prewarmed executable would never
    be reused. Every dispatch boundary routes its config through this."""
    return EngineConfig(*(_strong_f32(x) for x in cfg))


_DEFAULT_ECFG: "EngineConfig | None" = None


def default_engine_config() -> EngineConfig:
    """The default provider's composition: every filter on, the default score
    set at weight 1, MostAllocated off (algorithmprovider/defaults).
    Strong-typed and cached: see strong_engine_config."""
    global _DEFAULT_ECFG
    if _DEFAULT_ECFG is None:
        one, zero = 1.0, 0.0
        _DEFAULT_ECFG = strong_engine_config(EngineConfig(
            f_unsched=one, f_name=one, f_ports=one, f_node_affinity=one,
            f_fit=one, f_taints=one, f_interpod=one, f_spread=one,
            f_volrestrict=one, f_vollimits=one,
            w_node_affinity=one, w_taint=one, w_img=one, w_least=one,
            w_balanced=one, w_most=zero, w_interpod=one, w_even=one,
            w_ssel=one,
        ))
    return _DEFAULT_ECFG


def abstract_engine_config(sharding=None) -> EngineConfig:
    """The EngineConfig a dispatch passes, as ShapeDtypeStructs: what an
    ahead-of-time compile (sched/prewarm.py, fleet/tables.py) gives in its
    place."""
    import jax

    return EngineConfig(*(
        jax.ShapeDtypeStruct(np.shape(x), jnp.float32, sharding=sharding)
        for x in default_engine_config()))


def _on(flag: Array) -> Array:
    """A filter component is enforced when its flag ≥ 0.5 (f32 scalar)."""
    return jnp.asarray(flag, jnp.float32) >= 0.5


class StaticLattice(NamedTuple):
    mask: Array        # [SC, N] — static Filter conjunction
    node_match: Array  # [SC, N] — nodeSelector ∧ node-affinity only (spread eligibility)
    score: Array       # [SC, N] f32 — static Score sum (pref + taint + image)
    pref_score: Array  # [SC, N] f32 — preferred node affinity, 0..100-normalized
    taint_score: Array # [SC, N] f32 — taint PreferNoSchedule score, 0..100
    img_score: Array   # [SC, N] f32 — ImageLocality, 0..100


class CycleArrays(NamedTuple):
    """Per-cycle precomputed tensors fed to the assignment scan."""

    static: StaticLattice
    TM: Array        # [S, SC] term × class match
    has_anti: Array  # [SC, S] class anti-term membership
    CNT: Array       # [S, N] per-node term match counts (live carry seed)
    HOLD: Array      # [S, N] per-node anti-term holder counts (live carry seed)
    # [SC, TS, N] the node's domain of the constraint's key holds a node
    # eligible for the class (topospread.eligible_in_domain)
    ELN: Array
    WCOLS: Array     # [S, SC] f32 signed symmetric-preference weights per class
    WSYM: Array      # [S, N] f32 symmetric weight seed from existing pods
    ecfg: EngineConfig  # traced plugin composition (filters + score weights)
    # [D, 0]: no bytes; its static length is the domain-axis capacity the
    # cycle was built for (`D`), which no other array here has in its shape
    domain_axis: Array
    # [K, N, N] bf16 same-domain matrices (interpod.same_domain) where
    # state/dims.py domain_sum chose "product" at these shapes, else None:
    # built here, once a cycle, so no round of an engine's loop rebuilds them
    SAME: Array | None = None

    @property
    def D(self) -> int:
        return self.domain_axis.shape[0]


def _safe_row_gather(M: Array, ids: Array, default: bool) -> Array:
    """M: [SN, N]; ids: [...] with -1 ⇒ `default` row."""
    rows = M[jnp.maximum(ids, 0)]
    return jnp.where((ids >= 0)[..., None], rows, default)


def build_static(
    tables: ClusterTables, unschedulable_key: int, empty_val: int,
    ecfg: EngineConfig | None = None,
) -> StaticLattice:
    if ecfg is None:
        ecfg = default_engine_config()
    nodes, classes = tables.nodes, tables.classes

    MT = node_term_matrix(tables.nterms, nodes)  # [SN, N]

    # spec.nodeSelector (PodMatchNodeSelector half, predicates.go:879-886)
    nsel_ok = _safe_row_gather(MT, classes.nsel_term, True)  # [SC, N]

    # node affinity required: OR of terms (predicates.go:894-906); present but
    # term-less affinity matches nothing
    term_rows = _safe_row_gather(MT, classes.nterm_ids, False)  # [SC, T, N]
    aff_any = term_rows.any(axis=1)
    aff_ok = (~classes.aff_active)[:, None] | aff_any

    node_match = nsel_ok & aff_ok & nodes.valid[None, :]
    # spread eligibility always uses the raw node_match; the FILTER honors
    # the NodeAffinity plugin flag
    node_match_f = (node_match | ~_on(ecfg.f_node_affinity)) & nodes.valid[None, :]

    # taints (PodToleratesNodeTaints) + spec.unschedulable (CheckNodeUnschedulable)
    tol_ok, prefer_cnt, unsched_ok = taint_matrices(
        tables.tolsets, nodes, unschedulable_key, empty_val
    )
    ts = classes.tolset  # [SC]
    taint_ok = tol_ok[ts]  # [SC, N]
    unsched_pass = (~nodes.unschedulable)[None, :] | unsched_ok[ts][:, None]

    taint_ok_f = taint_ok | ~_on(ecfg.f_taints)
    unsched_f = unsched_pass | ~_on(ecfg.f_unsched)
    mask = node_match_f & taint_ok_f & unsched_f & classes.valid[:, None]

    # --- static scores ---
    # preferred node affinity (node_affinity.go:34-80): Σ weight·match, then
    # NormalizeReduce(100, false) per pod-class across nodes
    pref_rows = _safe_row_gather(MT, classes.pterm_ids, False)  # [SC, PT, N]
    w = jnp.where(classes.pterm_ids >= 0, classes.pterm_w, 0).astype(jnp.float32)
    pref_raw = (w[:, :, None] * pref_rows).sum(axis=1)  # [SC, N]
    mx = pref_raw.max(axis=1, keepdims=True)
    pref_score = jnp.where(mx > 0, pref_raw * 100.0 / jnp.maximum(mx, 1e-9), 0.0)

    taint_score = taint_toleration_score(prefer_cnt[ts])  # [SC, N]
    img_score = image_locality_static(tables)              # [SC, N]

    w = ecfg
    score = (pref_score * w.w_node_affinity + taint_score * w.w_taint
             + img_score * w.w_img)
    return StaticLattice(mask=mask, node_match=node_match,
                         score=score,
                         pref_score=pref_score, taint_score=taint_score,
                         img_score=img_score)


def build_cycle(
    tables: ClusterTables,
    existing: PodArrays,
    unschedulable_key: int,
    empty_val: int,
    D: int,
    hard_weight=1,
    ecfg: EngineConfig | None = None,
    copies: int = 1,
) -> CycleArrays:
    """Everything the scan needs, computed in one fused pass on device.
    The analog of RunPreFilterPlugins + GetPredicateMetadata
    (generic_scheduler.go:206, metadata.go:334) — but once per *cycle*, shared
    by every pod, instead of once per pod. `D` (domain-axis capacity) must be
    static under jit — pass via static_argnums/partial. `copies`: how many
    node tables the caller's program stacks over this one (a fleet tick's
    vmap over tenants), for the room `domain_sum` reckons with."""
    if ecfg is None:
        ecfg = default_engine_config()
    ecfg = EngineConfig(*[jnp.asarray(x, jnp.float32) for x in ecfg])
    static = build_static(tables, unschedulable_key, empty_val, ecfg)
    TM = term_class_matrix(tables.terms, tables.labelsets, tables.classes)
    S = TM.shape[0]
    N = tables.nodes.valid.shape[0]
    has_anti = class_term_membership(tables.classes.anti_terms, S)
    # the three per-node seeds share one pass over the existing pods
    M = class_node_hist(existing, TM.shape[1], N)
    CNT = per_node_counts(TM, M)
    HOLD = per_node_counts(has_anti.T, M)
    WCOLS = symmetric_weight_cols(tables.classes, S, hard_weight)
    WSYM = weighted_per_node(WCOLS, M)
    K = tables.nodes.domain.shape[1]
    SAME = same_domain(tables.nodes) \
        if domain_sum(N, K, copies) == "product" else None
    ELN = eligible_in_domain(static.node_match, tables.classes, tables.nodes,
                             D, SAME)
    return CycleArrays(static=static, TM=TM, has_anti=has_anti, CNT=CNT,
                       HOLD=HOLD, ELN=ELN, WCOLS=WCOLS, WSYM=WSYM, ecfg=ecfg,
                       SAME=SAME, domain_axis=jnp.zeros((D, 0), bool))
