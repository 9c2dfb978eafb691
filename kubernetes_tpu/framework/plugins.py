"""In-tree framework plugins — the tensor re-expression of
pkg/scheduler/framework/plugins/* wrapping the lattice ops.

Each filter plugin selects its per-predicate component from the shared
MaskComponents decomposition (computed once per fused cycle); each score
plugin returns a 0..100-normalized [P, N] tensor. Plugin names match the
reference's registry keys (framework/plugins/default_registry.go:57) so
Plugins configs written for the reference map 1:1.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..api.types import RES_CPU, RES_EPHEMERAL, RES_MEM
from ..ops.assign import mask_components
from ..ops.fit import resource_scores_row
from ..ops.interpod import soft_affinity_row
from ..ops.lattice import build_cycle
from ..ops.scores import (
    even_spread_soft_row,
    image_locality_static,
    selector_spread_row,
)
from .interface import (
    CycleState,
    FilterPlugin,
    Plugin,
    ScorePlugin,
    TensorContext,
)
from .runtime import Framework, Plugins, PluginSet, Registry


def build_context(tables, existing, pending, uk, ev, D) -> TensorContext:
    """Assemble the TensorContext for one fused cycle (PreFilter device half:
    build_cycle = GetPredicateMetadata analog, metadata.go:334)."""
    cyc = build_cycle(tables, existing, uk, ev, D)
    ctx = TensorContext(tables=tables, cyc=cyc, pending=pending)
    comp = mask_components(tables, cyc, pending)
    return ctx._replace(components=comp)


# --------------------------------------------------------------------------- #
# Filter plugins (framework/plugins/<dir>; predicates.go semantics)
# --------------------------------------------------------------------------- #


class NodeResourcesFit(FilterPlugin):
    """noderesources/fit.go — PodFitsResources (predicates.go:789)."""

    def filter_mask(self, state: CycleState, ctx: TensorContext):
        return ctx.components.fit


class NodeAffinity(FilterPlugin):
    """nodeaffinity/ — PodMatchNodeSelector (predicates.go:914): spec.nodeSelector
    ∧ required node affinity."""

    def filter_mask(self, state: CycleState, ctx: TensorContext):
        return ctx.components.node_match


class NodeName(FilterPlugin):
    """nodename/ — PodFitsHost (predicates.go:926)."""

    def filter_mask(self, state: CycleState, ctx: TensorContext):
        return ctx.components.host


class NodePorts(FilterPlugin):
    """nodeports/ — PodFitsHostPorts (predicates.go:1104)."""

    def filter_mask(self, state: CycleState, ctx: TensorContext):
        return ctx.components.ports


class TaintToleration(FilterPlugin, ScorePlugin):
    """tainttoleration/ — PodToleratesNodeTaints (predicates.go:1543) filter +
    PreferNoSchedule-counting score (taint_toleration.go)."""

    def filter_mask(self, state: CycleState, ctx: TensorContext):
        return ctx.components.taints

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        return ctx.cyc.static.taint_score[ctx.pending.cls]


class NodeUnschedulable(FilterPlugin):
    """nodeunschedulable/ — CheckNodeUnschedulable (predicates.go:1522).
    Evaluated jointly with taints in the lattice (spec.unschedulable is the
    synthetic node.kubernetes.io/unschedulable taint); the shared component
    keeps both names live for config parity."""

    def filter_mask(self, state: CycleState, ctx: TensorContext):
        return ctx.components.taints


class VolumeRestrictions(FilterPlugin):
    """volumerestrictions/ — NoDiskConflict (predicates.go:156-221)."""

    def filter_mask(self, state: CycleState, ctx: TensorContext):
        return ctx.components.volumes


class NodeVolumeLimits(FilterPlugin):
    """nodevolumelimits/ — the max-volume-count family
    (csi_volume_predicate.go:89; shares the fused volumes component with
    VolumeRestrictions — both are exact subsets of it)."""

    def filter_mask(self, state: CycleState, ctx: TensorContext):
        return ctx.components.volumes


class InterPodAffinity(FilterPlugin, ScorePlugin):
    """interpodaffinity/ — MatchInterPodAffinity (predicates.go:1212) filter +
    soft (anti)affinity score (interpod_affinity.go:119-215)."""

    def filter_mask(self, state: CycleState, ctx: TensorContext):
        return ctx.components.affinity & ctx.components.anti

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        tables, cyc = ctx.tables, ctx.cyc
        D = cyc.D
        return jax.vmap(
            lambda c: soft_affinity_row(
                c, tables.classes, tables.terms, cyc.CNT, tables.nodes, D,
                TM=cyc.TM, WSYM=cyc.WSYM, same=cyc.SAME)
        )(ctx.pending.cls)


class PodTopologySpread(FilterPlugin, ScorePlugin):
    """podtopologyspread/ — EvenPodsSpreadPredicate (predicates.go:1643)
    filter + the ScheduleAnyway score (even_pods_spread.go:106-227)."""

    def filter_mask(self, state: CycleState, ctx: TensorContext):
        return ctx.components.spread

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        tables, cyc = ctx.tables, ctx.cyc
        D = cyc.D
        return jax.vmap(
            lambda c: even_spread_soft_row(
                c, tables.classes, tables.terms, cyc.CNT, tables.nodes,
                cyc.static.node_match[c], D, cyc.SAME)
        )(ctx.pending.cls)


class SelectorSpread(ScorePlugin):
    """defaultpodtopologyspread/ — SelectorSpread across hosts and zones
    (priorities/selector_spreading.go:62-165; Pod.spread_selectors carries the
    Service/RC/RS/StatefulSet owner selectors the reference resolves via
    listers)."""

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        tables, cyc = ctx.tables, ctx.cyc
        D = cyc.D
        return jax.vmap(
            lambda c: selector_spread_row(
                c, tables.classes, cyc.CNT, tables.nodes, tables.zone_keys, D)
        )(ctx.pending.cls)


class ImageLocality(ScorePlugin):
    """imagelocality/ — spread-scaled image-size score
    (priorities/image_locality.go:39-92)."""

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        return ctx.cyc.static.img_score[ctx.pending.cls]


class NodeLabel(ScorePlugin):
    """nodelabel/ — presence/absence label preferences
    (priorities/node_label.go:46-71). Config: {"present": [...keys],
    "absent": [...keys]}; score = 100 × hits / #prefs."""

    def __init__(self, present=(), absent=()):
        self.present = tuple(present)
        self.absent = tuple(absent)

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        nodes = ctx.tables.nodes
        P = ctx.pending.valid.shape[0]
        N = nodes.valid.shape[0]
        prefs = len(self.present) + len(self.absent)
        if prefs == 0:
            return jnp.zeros((P, N), jnp.float32)
        # label-key ids resolved host-side by the config wiring
        # (SchedulerServer interns self.present/self.absent into
        # _present_ids/_absent_ids). A 'present' key missing from the vocab
        # can match no node; an 'absent' key missing from the vocab is
        # absent from every node — both handled without touching the -1
        # padding in label_keys.
        hits = jnp.zeros((N,), jnp.float32)
        for kid in getattr(self, "_present_ids", ()):
            if kid >= 0:
                hits = hits + (nodes.label_keys == kid).any(-1)
        for kid in getattr(self, "_absent_ids", ()):
            if kid >= 0:
                hits = hits + ~((nodes.label_keys == kid).any(-1))
            else:
                hits = hits + 1.0
        score = 100.0 * hits / prefs
        return jnp.broadcast_to(score[None, :], (P, N))


#: a resource of RequestedToCapacityRatio's weight map -> its slot of the R
#: axis, for the names with a fixed slot (api/types.py RES_*); an extended
#: resource's slot follows its id in the vocab (state/vocab.py resources)
_FIXED_RESOURCE_SLOT = {"cpu": RES_CPU, "memory": RES_MEM,
                        "ephemeral-storage": RES_EPHEMERAL}


def rtc_arguments(args: Optional[dict]):
    """RequestedToCapacityRatioArguments (scheduler/api/types.go; the
    plugin's args of later versions carry the same two fields) ->
    `(points, resources)`: the shape as (utilization, score) pairs with the
    score scaled from the argument's 0..10 to the 0..100 of a node score
    (factory/plugins.go buildScoringFunctionShapeFromRequestedToCapacity
    RatioArguments), the weight map as (name, weight) pairs, cpu 1 and
    memory 1 where the argument names none. Raises ValueError on what
    upstream's validation refuses, and on what the fused row cannot carry."""
    from ..ops.lattice import RTC_POINTS

    args = args or {}
    raw = args.get("shape") \
        or [{"utilization": 0, "score": 10}, {"utilization": 100, "score": 0}]
    points = []
    for p in raw:
        u, sc = (p["utilization"], p["score"]) if isinstance(p, dict) else p
        if int(u) != u or int(sc) != sc:
            raise ValueError(f"shape point {p!r}: integers wanted")
        points.append((int(u), int(sc)))
    if len(points) > RTC_POINTS:
        raise ValueError(f"shape has {len(points)} points, at most "
                         f"{RTC_POINTS} are carried")
    for i, (u, sc) in enumerate(points):
        if not 0 <= u <= 100 or not 0 <= sc <= 10:
            raise ValueError(f"shape point {(u, sc)}: utilization 0..100 "
                             "and score 0..10 wanted")
        if i and u <= points[i - 1][0]:
            raise ValueError("shape utilization values must be sorted "
                             "and strictly increasing")
    resources = []
    for r in args.get("resources") or [{"name": "cpu", "weight": 1},
                                       {"name": "memory", "weight": 1}]:
        name, weight = (r["name"], r.get("weight", 1)) \
            if isinstance(r, dict) else r
        if int(weight) != weight or weight < 1:
            raise ValueError(f"resource {name!r}: weight {weight!r} < 1")
        if name not in _FIXED_RESOURCE_SLOT and "/" not in name:
            raise ValueError(f"resource {name!r} is neither cpu, memory, "
                             "ephemeral-storage nor an extended resource")
        resources.append((name, int(weight)))
    return tuple((u, sc * 10) for u, sc in points), tuple(resources)


def rtc_arrays(points, resources, resource_slot):
    """The three `EngineConfig.rtc_*` arrays of `rtc_arguments`' result.
    `resource_slot(name)` gives an extended resource's slot of the R axis
    (interning it: `NUM_FIXED_RES + vocab.resources.intern(name)`)."""
    import numpy as np

    from ..ops.lattice import RTC_POINTS, RTC_SLOTS

    pts = list(points) + [points[-1]] * (RTC_POINTS - len(points))
    w = np.zeros((RTC_SLOTS,), np.float32)
    for name, weight in resources:
        slot = _FIXED_RESOURCE_SLOT.get(name)
        if slot is None:
            slot = resource_slot(name)
        if slot >= RTC_SLOTS:
            raise ValueError(f"resource {name!r} sits in slot {slot} of the "
                             f"resource axis; {RTC_SLOTS} are carried")
        w[slot] += weight
    return (np.asarray([p[0] for p in pts], np.float32),
            np.asarray([p[1] for p in pts], np.float32), w)


class RequestedToCapacityRatio(ScorePlugin):
    """requestedtocapacityratio/ — broken-linear utilization shape over the
    resources of a weight map (priorities/requested_to_capacity_ratio.go).
    Config: the argument's `shape` ([{"utilization": u, "score": 0..10}])
    and `resources` ([{"name", "weight"}], default cpu 1, memory 1). The
    score itself lives in the engines' fused row (ops/fit.py rtc_score_row,
    weight `EngineConfig.w_rtc`), which this plugin evaluates against the
    cycle-start state: the spec the tests hold the fused row to."""

    def __init__(self, shape=None, resources=None, resource_slot=None):
        self.points, self.resources = rtc_arguments(
            {"shape": shape, "resources": resources})
        # the slot of an extended resource of the map; the config wiring
        # (SchedulerServer) hands the encoder's vocab in
        self.resource_slot = resource_slot

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        from ..ops.fit import rtc_score_row

        tables = ctx.tables
        xs, ys, w = (jnp.asarray(a) for a in rtc_arrays(
            self.points, self.resources, self.resource_slot))

        def row(c):
            req_vec = tables.reqs.vec[tables.classes.rid[c]]
            return rtc_score_row(req_vec, tables.nodes.used,
                                 tables.nodes.alloc, xs, ys, w)

        return jax.vmap(row)(ctx.pending.cls)


class ResourceLimits(ScorePlugin):
    """noderesources/resource_limits.go — tie-break score 1 when the node can
    satisfy the pod's cpu or memory LIMITS, else 0 (feature-gated off by
    default in the reference, kube_features.go ResourceLimitsPriorityFunction)."""

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        tables = ctx.tables
        classes = tables.classes

        def row(c):
            lim = classes.lim_rid[c]
            vec = tables.reqs.vec[jnp.maximum(lim, 0)]
            cap = tables.nodes.alloc
            cpu_ok = (vec[0] > 0) & (cap[:, 0] > 0) & (vec[0] <= cap[:, 0])
            mem_ok = (vec[1] > 0) & (cap[:, 1] > 0) & (vec[1] <= cap[:, 1])
            return jnp.where((lim >= 0) & (cpu_ok | mem_ok), 1.0, 0.0)

        return jax.vmap(row)(ctx.pending.cls)


# --------------------------------------------------------------------------- #
# Score plugins
# --------------------------------------------------------------------------- #


class _ResourceScoreBase(ScorePlugin):
    _index = 0  # 0 = least, 1 = balanced, 2 = most

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        tables = ctx.tables

        def row(c):
            req_vec = tables.reqs.vec[tables.classes.rid[c]]
            return resource_scores_row(req_vec, tables.nodes.used, tables.nodes.alloc)

        triple = jax.vmap(row)(ctx.pending.cls)
        return triple[self._index]


class NodeResourcesLeastAllocated(_ResourceScoreBase):
    """noderesources/least_allocated.go — spread by free capacity."""

    _index = 0


class NodeResourcesBalancedAllocation(_ResourceScoreBase):
    """noderesources/balanced_allocation.go — minimize cpu/mem fraction skew."""

    _index = 1


class NodeResourcesMostAllocated(_ResourceScoreBase):
    """noderesources/most_allocated.go — bin packing: (total/cap)×100 averaged
    over cpu+memory (most_requested.go:52-70); shares resource_scores_row with
    least/balanced so the formula lives once."""

    _index = 2


class NodePreferAvoidPods(ScorePlugin):
    """nodepreferavoidpods/ — nodes annotated avoid-pods score 0, others 100
    (node_prefer_avoid_pods.go). The annotation rides NodeArrays.avoid
    (encoded from scheduler.alpha.kubernetes.io/preferAvoidPods).
    Deviation: the reference applies the avoidance only to pods controlled
    by an RC/RS (checks the controllerRef kind); here every pod avoids the
    node — the annotation's operational intent (drain-ish bias) at class
    granularity."""

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        avoid = ctx.tables.nodes.avoid
        N = ctx.tables.nodes.valid.shape[0]
        P = ctx.pending.valid.shape[0]
        return jnp.broadcast_to(
            jnp.where(avoid[None, :], 0.0, 100.0), (P, N)).astype(jnp.float32)


class NodeAffinityScore(ScorePlugin):
    """nodeaffinity preferred terms score (priorities/node_affinity.go:34)."""

    def score_matrix(self, state: CycleState, ctx: TensorContext):
        return ctx.cyc.static.pref_score[ctx.pending.cls]


# --------------------------------------------------------------------------- #
# registry + defaults (default_registry.go:57 NewDefaultRegistry)
# --------------------------------------------------------------------------- #


# score plugins whose semantics are compiled INTO the fused engines via
# EngineConfig weights (ops/lattice.py); anything else configured at the
# score point reaches the fused path as a per-class bias matrix
# (extra_score_plugins → sched/cycle.py)
FUSED_SCORE_PLUGINS = frozenset({
    "NodeResourcesLeastAllocated", "NodeResourcesBalancedAllocation",
    "NodeResourcesMostAllocated", "NodeAffinityScore", "TaintToleration",
    "InterPodAffinity", "PodTopologySpread", "SelectorSpread", "ImageLocality",
    "RequestedToCapacityRatio",
    # registry alias for SelectorSpread (default_registry.go keeps both
    # names); it must not leak into the class-pure extras path — its score
    # depends on in-cycle placements
    "DefaultPodTopologySpread",
})


class Coscheduling(Plugin):
    """Gang scheduling on the Permit machinery — the host per-pod analog of
    the device gang engine (ops/gang.py). Semantics follow the out-of-tree
    sig-scheduling coscheduling plugin (the reference ships none in-tree:
    the Permit wait/allow surface at framework/v1alpha1/interface.go:339 +
    waiting_pods_map.go IS its extension hook for exactly this):

      * Reserve tracks a group's assumed members;
      * Permit WAITs each member (with `timeout`) until the group's
        minMember count is reserved, then the arriving member ALLOWs every
        waiting sibling (allow_waiting_pod) and proceeds itself;
      * the waiting-map timeout rejecting a parked member unreserves it —
        a group that never fills releases everything it held.

    Wiring: the Scheduler auto-wires `on_release` (its complete_waiting) and
    `bound_count` (its cache's group_bound_count) when this plugin is in the
    permit set — see Scheduler.__init__; tests exercising the framework
    standalone can leave both unset and quorum falls back to the plugin's
    own reservation ledger."""

    name = "Coscheduling"

    def __init__(self, timeout: float = 30.0):
        self.timeout = timeout
        self.handle = None        # Framework runtime (allow_waiting_pod)
        self.on_release = None    # Scheduler.complete_waiting
        self.groups: dict = {}    # group key → authoritative minMember
        self.bound_count = None   # callable: group key → assumed+bound members
        self._reserved: dict = {}  # group key → in-flight reserved pod keys

    def register_group(self, key: str, min_member: int) -> None:
        """PodGroup object registration (overrides pod-carried hints)."""
        self.groups[key] = int(min_member)

    def _min_member(self, gk: str, pod) -> int:
        return self.groups.get(gk) or max(pod.min_member, 1)

    def reserve(self, state, pod, node_name):
        gk = pod.group_key
        if gk:
            self._reserved.setdefault(gk, set()).add(pod.key)
        return None

    def unreserve(self, state, pod, node_name):
        gk = pod.group_key
        if gk:
            self._reserved.get(gk, set()).discard(pod.key)

    def permit(self, state, pod, node_name):
        from .interface import Code, Status

        gk = pod.group_key
        if not gk:
            return None, 0.0
        # quorum: members assumed in the cache (covers every reserved member
        # — assume precedes Reserve — PLUS members bound in earlier cycles,
        # and self-heals when group pods are deleted). The plugin's own
        # ledger is the fallback for cache-less standalone use.
        if self.bound_count is not None:
            have = int(self.bound_count(gk))
        else:
            have = len(self._reserved.get(gk, ()))
        if have >= self._min_member(gk, pod):
            # quorum reached: release every waiting sibling, admit this one,
            # and retire the group's in-flight ledger (released members are
            # bound from here on — bound_count keeps counting them)
            waiting = [k for k in self._reserved.pop(gk, ()) if k != pod.key]
            if self.handle is not None:
                for key in waiting:
                    if self.handle.allow_waiting_pod(key, self.name) and \
                            self.on_release is not None:
                        self.on_release(key)
            return None, 0.0
        return Status(Code.WAIT, f"gang {gk}: {have}/"
                      f"{self._min_member(gk, pod)} members reserved"), \
            self.timeout


def extra_score_plugins(framework) -> tuple:
    """(plugin, weight) pairs for configured score plugins OUTSIDE the fused
    set — NodeLabel, ResourceLimits, NodePreferAvoidPods, or any custom
    registration. These are class-pure
    (their scores depend only on (class, node), not on in-cycle placement),
    so the fused dispatch evaluates them once per cycle as a [SC, N] bias
    added to the static score lattice."""
    if framework is None:
        return ()
    return tuple(
        (pl, float(getattr(pl, "weight", 1)))
        for pl in framework.score_plugins
        if getattr(pl, "name", type(pl).__name__) not in FUSED_SCORE_PLUGINS
    )


def _make_node_label(cfg: dict) -> "NodeLabel":
    """NodeLabel needs vocab ids for its configured label keys; the config
    loader resolves them (present_ids/absent_ids). String keys are kept for
    introspection."""
    p = NodeLabel(present=cfg.get("present", ()), absent=cfg.get("absent", ()))
    p._present_ids = tuple(cfg.get("present_ids", ()))
    p._absent_ids = tuple(cfg.get("absent_ids", ()))
    return p


def default_registry() -> Registry:
    return {
        "NodeResourcesFit": lambda cfg: NodeResourcesFit(),
        "NodeAffinity": lambda cfg: NodeAffinity(),
        "NodeName": lambda cfg: NodeName(),
        "NodePorts": lambda cfg: NodePorts(),
        "NodeUnschedulable": lambda cfg: NodeUnschedulable(),
        "TaintToleration": lambda cfg: TaintToleration(),
        "InterPodAffinity": lambda cfg: InterPodAffinity(),
        "PodTopologySpread": lambda cfg: PodTopologySpread(),
        "NodeResourcesLeastAllocated": lambda cfg: NodeResourcesLeastAllocated(),
        "NodeResourcesBalancedAllocation": lambda cfg: NodeResourcesBalancedAllocation(),
        "NodeResourcesMostAllocated": lambda cfg: NodeResourcesMostAllocated(),
        "NodePreferAvoidPods": lambda cfg: NodePreferAvoidPods(),
        "NodeAffinityScore": lambda cfg: NodeAffinityScore(),
        "VolumeRestrictions": lambda cfg: VolumeRestrictions(),
        "NodeVolumeLimits": lambda cfg: NodeVolumeLimits(),
        "SelectorSpread": lambda cfg: SelectorSpread(),
        "DefaultPodTopologySpread": lambda cfg: SelectorSpread(),
        "ImageLocality": lambda cfg: ImageLocality(),
        "NodeLabel": lambda cfg: _make_node_label(cfg or {}),
        "RequestedToCapacityRatio": lambda cfg: RequestedToCapacityRatio(
            shape=(cfg or {}).get("shape"),
            resources=(cfg or {}).get("resources")),
        "NodeResourcesResourceLimits": lambda cfg: ResourceLimits(),
        "Coscheduling": lambda cfg: Coscheduling(
            timeout=float((cfg or {}).get("permitWaitingTimeSeconds", 30.0))),
    }


def default_plugins() -> Plugins:
    """The default provider's plugin set (algorithmprovider/defaults +
    default_registry.go ConfigProducer mapping)."""
    return Plugins(
        filter=PluginSet(enabled=[
            "NodeUnschedulable", "NodeName", "NodePorts", "NodeAffinity",
            "NodeResourcesFit", "TaintToleration", "InterPodAffinity",
            "PodTopologySpread", "VolumeRestrictions", "NodeVolumeLimits",
        ]),
        score=PluginSet(enabled=[
            "NodeResourcesLeastAllocated", "NodeResourcesBalancedAllocation",
            "NodeAffinityScore", "TaintToleration", "InterPodAffinity",
            "PodTopologySpread", "SelectorSpread", "ImageLocality",
        ]),
    )


def default_framework(
    plugins: Optional[Plugins] = None,
    plugin_config: Optional[dict] = None,
    score_weights: Optional[dict] = None,
) -> Framework:
    return Framework(
        registry=default_registry(),
        plugins=plugins or default_plugins(),
        plugin_config=plugin_config,
        score_weights=score_weights,
    )
