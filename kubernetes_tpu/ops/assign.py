"""Batched assignment: the whole scheduling cycle as one lax.scan on device.

The reference schedules one pod per `scheduleOne` call (scheduler.go:596-763):
snapshot → filter over nodes (16 goroutines) → score → selectHost → assume.
Each pod's placement updates the cache before the next pod is considered —
sequential *semantics* are load-bearing (two pods landing on one node must see
each other's resource usage and affinity counts).

Here the entire pending batch is scheduled in ONE device dispatch: a lax.scan
over pods in queue order (priority desc, creation asc — the activeQ comparator,
internal/queue/scheduling_queue.go:119-138 + util.GetPodPriority). The scan
carry is the assume-cache state: per-node used resources, port bitsets, and the
affinity/spread count tables. Per step: O(N) rows of dynamic checks + gathers
into the precomputed static [SC, N] lattice. This preserves the reference's
sequential assume semantics exactly while amortizing all O(SC·N·…) work outside
the loop.

Deviation (documented in docs/PARITY.md): ties in the max score pick the
lowest node index (deterministic) instead of the reference's reservoir-random
selectHost (generic_scheduler.go:290-311).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..state.arrays import Array, ClusterTables, PodArrays
from ..state.dims import affinity_agg
from .fit import fit_row, resource_scores_row, rtc_score_row
from .interpod import (TermCounts, affinity_rows, soft_affinity_row,
                       term_domain_counts)
from .lattice import CycleArrays
from .ports import port_conflict_row
from .scores import even_spread_soft_row, selector_spread_row
from .topospread import SpreadCounts, spread_counts, spread_row
from .volumes import volume_components_row, volume_ok_row


class AssignState(NamedTuple):
    used: Array  # [N, R] i32
    ppa: Array   # [N, PWp] u32 — (proto,port) pairs in use (any IP)
    ppw: Array   # [N, PWp] u32 — wildcard-IP pairs in use
    ppt: Array   # [N, PWt] u32 — exact triples in use
    CNT: Array   # [S, N] i32 — per-node term match counts
    HOLD: Array  # [S, N] i32 — per-node anti-term holders
    WSYM: Array  # [S, N] f32 — signed symmetric soft-affinity weights
    vol_any: Array  # [N, VW] u32 — attached volumes (NoDiskConflict/limits)
    vol_rw: Array   # [N, VW] u32 — attached read-write
    vol_cnt: Array  # [N, DR] i32 — attached volumes of one pod alone


class AssignResult(NamedTuple):
    node: Array       # [P] i32 — chosen node index, -1 unschedulable
    feasible: Array   # [P] bool
    state: AssignState
    # ops/gang.py GangVerdict on a gang-bearing batch; None (no pytree
    # leaf: the gang-free programs are unchanged) everywhere else
    gang: Any = None
    # scalar i32, the rounds the waves engine's loop ran (ops/waves.py);
    # None from the scan and from the gang loop, which counts its own
    rounds: Any = None
    # [3] i32 from the waves engine (ops/waves.py, "Fill"): the classes of
    # the batch that fill, the pods they placed, the rounds in which one
    # placed any; None from the scan and from the gang loop
    fill: Any = None


def queue_order(pods: PodArrays) -> Array:
    """activeQ pop order: valid first, then priority desc, then creation asc
    (scheduling_queue.go activeQComp → podutil.GetPodPriority + timestamp)."""
    return jnp.lexsort((pods.creation, -pods.priority, ~pods.valid))


def assign_step(
    tables: ClusterTables,
    cyc: CycleArrays,
    state: AssignState,
    c: Array,
    p_valid: Array,
    node_name_req: Array,
    pin: Array = -1,
) -> Tuple[AssignState, Array, Array]:
    """ONE pod's Filter → Score → selectHost → assume against a live state —
    the body of the sequential scan. Returns (new state, node index or -1,
    feasible)."""
    classes = tables.classes
    req_vec = tables.reqs.vec[classes.rid[c]]
    ps = classes.portset[c]
    psafe = jnp.maximum(ps, 0)

    mask = pod_mask_row(tables, cyc, state, c, node_name_req, p_valid,
                        pin=pin)

    # ---- Score row (weighted sum; component weights/enables come from
    #      the traced EngineConfig — generic_scheduler.go:823-832) ----
    score = score_row(tables, cyc, state, c)
    score = jnp.where(mask, score, -jnp.inf)

    choice = jnp.argmax(score)
    feasible = mask.any() & p_valid
    node = jnp.where(feasible, choice, -1)

    # ---- assume: commit to carry (cache.AssumePod analog) ----
    add = jnp.where(feasible, req_vec, 0)
    used = state.used.at[choice].add(add)

    live_ps = feasible & (ps >= 0)
    pw = jnp.where(live_ps, tables.portsets.pair_words[psafe], 0)
    ww = jnp.where(live_ps, tables.portsets.wild_words[psafe], 0)
    tw = jnp.where(live_ps, tables.portsets.trip_words[psafe], 0)
    ppa = state.ppa.at[choice].set(state.ppa[choice] | pw)
    ppw = state.ppw.at[choice].set(state.ppw[choice] | ww)
    ppt = state.ppt.at[choice].set(state.ppt[choice] | tw)

    # affinity/spread counts: this pod now matches its terms at its node
    inc = (cyc.TM[:, c] & feasible).astype(jnp.int32)   # [S]
    CNT = state.CNT.at[:, choice].add(inc)
    inc_h = (cyc.has_anti[c] & feasible).astype(jnp.int32)
    HOLD = state.HOLD.at[:, choice].add(inc_h)
    WSYM = state.WSYM.at[:, choice].add(
        jnp.where(feasible, cyc.WCOLS[:, c], 0.0))

    vs = tables.classes.volset[c]
    live_vs = feasible & (vs >= 0)
    va = jnp.where(live_vs, tables.volsets.any_words[jnp.maximum(vs, 0)], 0)
    vr = jnp.where(live_vs, tables.volsets.rw_words[jnp.maximum(vs, 0)], 0)
    vol_any = state.vol_any.at[choice].set(state.vol_any[choice] | va)
    vol_rw = state.vol_rw.at[choice].set(state.vol_rw[choice] | vr)
    vol_cnt = state.vol_cnt.at[choice].add(
        jnp.where(feasible, tables.classes.vol_priv[c], 0))

    return AssignState(used, ppa, ppw, ppt, CNT, HOLD, WSYM,
                       vol_any, vol_rw, vol_cnt), node, feasible


def assign_batch(
    tables: ClusterTables,
    cyc: CycleArrays,
    pods: PodArrays,
    init: AssignState,
) -> AssignResult:
    order = queue_order(pods)

    def step(state: AssignState, idx):
        state, node, feasible = assign_step(
            tables, cyc, state, pods.cls[idx], pods.valid[idx],
            pods.node_name_req[idx], pods.pin[idx])
        return state, (node, feasible)

    final, (nodes_sorted, feas_sorted) = jax.lax.scan(step, init, order)

    P = pods.valid.shape[0]
    node_out = jnp.full((P,), -1, jnp.int32).at[order].set(nodes_sorted)
    feas_out = jnp.zeros((P,), bool).at[order].set(feas_sorted)
    return AssignResult(node=node_out, feasible=feas_out, state=final)


def state_affinity_table(
    tables: ClusterTables, cyc: CycleArrays, state: AssignState, rows: int
) -> TermCounts | None:
    """What the row functions below take as `table`: `state`'s [S, N]
    in-domain count table (interpod.term_domain_counts) where a program that
    evaluates `rows` classes against it would ask for at least as many
    aggregates row by row (state/dims.py affinity_agg: "term"), else None
    and each row aggregates its own slots ("row")."""
    classes = tables.classes
    slots = (classes.aff_terms.shape[1] + classes.anti_terms.shape[1]
             + classes.paff_terms.shape[1] + classes.panti_terms.shape[1])
    if affinity_agg(rows, slots, cyc.TM.shape[0]) == "row":
        return None
    return term_domain_counts(tables.terms, state.CNT, state.HOLD, state.WSYM,
                              tables.nodes, cyc.D, cyc.SAME)


def state_spread_counts(
    tables: ClusterTables, cyc: CycleArrays, state: AssignState, rows: int
) -> SpreadCounts | None:
    """What the row functions below take as `spread`: topology spread's
    counts of `state` for EVERY (class, slot), one in-domain sum of SC x TS
    rows (topospread.spread_counts), where a program that evaluates `rows`
    classes against the state would ask for at least as many row by row — the
    rule pod affinity's table goes by — else None and each row sums its own
    class's slots. The Filter row, the soft score and the waves round's
    admission cap all read this one."""
    classes = tables.classes
    SC, TS = classes.tsc_term.shape
    if affinity_agg(rows, TS, SC * TS) == "row":
        return None
    return spread_counts(jnp.arange(SC), classes, tables.terms, state.CNT,
                         cyc.static.node_match, cyc.ELN, tables.nodes, cyc.D,
                         cyc.SAME)


def mask_context_row(
    tables: ClusterTables,
    cyc: CycleArrays,
    state: AssignState,
    cls: Array,
    node_name_req: Array,
    valid: Array,
    table: TermCounts | None = None,
    spread: SpreadCounts | None = None,
    pin: Array = -1,
) -> Array:
    """The Filter components that do not move as replicas of a
    self-interaction-free class land: the static lattice, inter-pod
    affinity/anti-affinity (counts only move at placed nodes, through terms
    such a class never reads), hard topology spread, spec.nodeName, the
    pod's pin, and pod validity. pod_mask_row composes it with
    mask_dynamic_row per pod. `table` is `state_affinity_table(state)` and
    `spread` `state_spread_counts(state)` where the caller built them; `pin`
    is the pod's `PodArrays.pin` (-1: none, as a class-level caller says
    it)."""
    from .lattice import _on

    nodes, classes, terms = tables.nodes, tables.classes, tables.terms
    ecfg = cyc.ecfg
    D = cyc.D
    aff_ok, anti_ok = affinity_rows(
        cls, classes, terms, cyc.TM, state.CNT, state.HOLD, nodes, D, table,
        cyc.SAME)
    interpod_ok = (aff_ok & anti_ok) | ~_on(ecfg.f_interpod)
    spread_ok = spread_row(
        cls, classes, terms, cyc.TM, state.CNT, cyc.ELN,
        cyc.static.node_match[cls], nodes, D, cyc.SAME, spread,
    ) | ~_on(ecfg.f_spread)
    host_ok = (node_name_req < 0) | (nodes.name_id == node_name_req) \
        | ~_on(ecfg.f_name)
    return (cyc.static.mask[cls] & interpod_ok & spread_ok & host_ok
            & pin_plane(tables, cyc, pin) & valid)


def pin_plane(tables: ClusterTables, cyc: CycleArrays, pin: Array) -> Array:
    """[N] the pod's pin as a Filter plane: the node affinity term
    `metadata.name In [pin]` that every term of the pod carried
    (state/encode.py pin_name), under the NodeAffinity plugin's flag. True
    everywhere for a pod without one; false everywhere for a pin no node
    bears."""
    from .lattice import _on

    return (pin < 0) | (tables.nodes.name_id == pin) \
        | ~_on(cyc.ecfg.f_node_affinity)


def fit_plane(tables: ClusterTables, cyc: CycleArrays, cls: Array,
              used: Array) -> Array:
    """PodFitsResources plane [N] incl. the plugin flag — the ONE
    composition shared by the engines' dynamic mask and the explain
    attribution (drift between the two would make reason counts lie)."""
    from .lattice import _on

    req_vec = tables.reqs.vec[tables.classes.rid[cls]]
    return fit_row(req_vec, used, tables.nodes.alloc, tables.nodes.valid) \
        | ~_on(cyc.ecfg.f_fit)


def ports_plane(tables: ClusterTables, cyc: CycleArrays, cls: Array,
                ppa: Array, ppw: Array, ppt: Array) -> Array:
    """PodFitsHostPorts plane [N] incl. the plugin flag (shared, see
    fit_plane)."""
    from .lattice import _on

    ps = tables.classes.portset[cls]
    psafe = jnp.maximum(ps, 0)
    conflict = port_conflict_row(
        tables.portsets.wild_words[psafe],
        tables.portsets.pair_words[psafe],
        tables.portsets.trip_words[psafe],
        ppa, ppw, ppt,
    )
    return (ps < 0) | ~conflict | ~_on(cyc.ecfg.f_ports)


def volumes_plane(tables: ClusterTables, cyc: CycleArrays, cls: Array,
                  vol_any: Array, vol_rw: Array, vol_cnt: Array) -> Array:
    """NoDiskConflict + volume-limits plane [N] incl. the plugin flags
    (shared, see fit_plane)."""
    from .lattice import _on

    vconf_free, vlimit_ok = volume_components_row(
        tables, vol_any, vol_rw, vol_cnt, cls)
    return (vconf_free | ~_on(cyc.ecfg.f_volrestrict)) \
        & (vlimit_ok | ~_on(cyc.ecfg.f_vollimits))


def mask_dynamic_row(
    tables: ClusterTables,
    cyc: CycleArrays,
    cls: Array,
    used: Array,
    ppa: Array, ppw: Array, ppt: Array,
    vol_any: Array, vol_rw: Array, vol_cnt: Array,
) -> Array:
    """The Filter components that move as replicas of the SAME class land:
    resources, host ports, volumes — all strictly per-node functions of the
    passed state planes; the per-pod scan calls it (via pod_mask_row) with
    the live carry. Composed from the same per-plane helpers the explain
    attribution decomposes."""
    return (fit_plane(tables, cyc, cls, used)
            & ports_plane(tables, cyc, cls, ppa, ppw, ppt)
            & volumes_plane(tables, cyc, cls, vol_any, vol_rw, vol_cnt))


def pod_mask_row(
    tables: ClusterTables,
    cyc: CycleArrays,
    state: AssignState,
    cls: Array,
    node_name_req: Array,
    valid: Array,
    table: TermCounts | None = None,
    spread: SpreadCounts | None = None,
    pin: Array = -1,
) -> Array:
    """Full Filter mask [N] for one pod against a given assume-state — the
    tensor analog of podFitsOnNode (generic_scheduler.go:628-706). Shared by
    the assignment scan and the golden-test / extender surfaces. Each
    component honors its EngineConfig plugin flag (a disabled filter plugin
    never blocks, matching CreateFromKeys composition). Composed from the
    run-constant context half and the per-placement dynamic half — boolean
    conjunction, so the regrouping is exact."""
    return (
        mask_context_row(tables, cyc, state, cls, node_name_req, valid,
                         table, spread, pin)
        & mask_dynamic_row(tables, cyc, cls, state.used,
                           state.ppa, state.ppw, state.ppt,
                           state.vol_any, state.vol_rw, state.vol_cnt)
    )


class ScoreContext(NamedTuple):
    """The Score components that stay fixed across a self-interaction-free
    replica run: the count/weight-aggregated rows whose inputs (CNT/WSYM at
    terms the class reads) its own placements cannot move."""

    soft_ip: Array    # [N] soft inter-pod affinity, min/max-normalized
    even_soft: Array  # [N] EvenPodsSpread ScheduleAnyway score
    ssel: Array       # [N] SelectorSpread score


def score_context_row(
    tables: ClusterTables,
    cyc: CycleArrays,
    state: AssignState,
    cls: Array,
    table: TermCounts | None = None,
    spread: SpreadCounts | None = None,
) -> ScoreContext:
    nodes, classes, terms = tables.nodes, tables.classes, tables.terms
    D = cyc.D
    soft_ip = soft_affinity_row(cls, classes, terms, state.CNT, nodes, D,
                                TM=cyc.TM, WSYM=state.WSYM, table=table,
                                same=cyc.SAME)
    even_soft = even_spread_soft_row(
        cls, classes, terms, state.CNT, nodes, cyc.static.node_match[cls], D,
        cyc.SAME, spread)
    ssel = selector_spread_row(
        cls, classes, state.CNT, nodes, tables.zone_keys, D)
    return ScoreContext(soft_ip=soft_ip, even_soft=even_soft, ssel=ssel)


def score_combine_row(
    tables: ClusterTables,
    cyc: CycleArrays,
    cls: Array,
    used: Array,
    ctx: ScoreContext,
) -> Array:
    """The exact weighted-sum expression tree of the Score row, parameterized
    by the per-node `used` plane. Both engines go through this one function
    (score_row), so the float op sequence (and therefore every rounding)
    is identical by construction."""
    nodes, classes = tables.nodes, tables.classes
    w = cyc.ecfg
    req_vec = tables.reqs.vec[classes.rid[cls]]
    least, balanced, most = resource_scores_row(req_vec, used, nodes.alloc)
    # (a configuration without the priority, the default provider's among
    # them, does not pay for its arithmetic: the weight is traced, so the
    # program is one and the branch is the device's to take)
    rtc = jax.lax.cond(
        w.w_rtc != 0,
        lambda: rtc_score_row(req_vec, used, nodes.alloc, w.rtc_x, w.rtc_y,
                              w.rtc_w),
        lambda: jnp.zeros(used.shape[:1], jnp.float32))
    return (cyc.static.score[cls] + least * w.w_least
            + balanced * w.w_balanced + most * w.w_most
            + ctx.soft_ip * w.w_interpod + ctx.even_soft * w.w_even
            + ctx.ssel * w.w_ssel + rtc * w.w_rtc)


def score_row(
    tables: ClusterTables,
    cyc: CycleArrays,
    state: AssignState,
    cls: Array,
    table: TermCounts | None = None,
    spread: SpreadCounts | None = None,
) -> Array:
    """Full Score row [N] for one pod class against a live assume-state —
    prioritizeNodes' weighted sum (generic_scheduler.go:714-869) with the
    EngineConfig carrying per-plugin weights. Shared by all engines and the
    score-matrix surface."""
    return score_combine_row(
        tables, cyc, cls, state.used,
        score_context_row(tables, cyc, state, cls, table, spread))


def feasible_matrix(
    tables: ClusterTables, cyc: CycleArrays, pods: PodArrays
) -> Array:
    """[P, N] Filter mask for every pending pod against the *initial* state
    (no assignment feedback) — findNodesThatFit (generic_scheduler.go:473) as
    one vmapped tensor, used for golden tests and the extender Filter verb."""
    state = initial_state(tables, cyc)
    P = pods.valid.shape[0]
    table = state_affinity_table(tables, cyc, state, P)
    spread = state_spread_counts(tables, cyc, state, P)
    return jax.vmap(
        lambda c, nnr, v, pin: pod_mask_row(tables, cyc, state, c, nnr, v,
                                            table, spread, pin)
    )(pods.cls, pods.node_name_req, pods.valid, pods.pin)


class MaskComponents(NamedTuple):
    """Per-predicate [P, N] masks for failure diagnosis — the tensor analog of
    PredicateFailureReason lists (predicates.go error types). Component names
    follow the reference predicate names (algorithm/predicates/error.go)."""

    node_match: Array   # MatchNodeSelector / node affinity
    taints: Array       # PodToleratesNodeTaints (incl. CheckNodeUnschedulable)
    fit: Array          # PodFitsResources
    ports: Array        # PodFitsHostPorts
    affinity: Array     # MatchInterPodAffinity (required affinity half)
    anti: Array         # MatchInterPodAffinity (anti-affinity half)
    spread: Array       # EvenPodsSpread
    host: Array         # PodFitsHost (spec.nodeName)
    volumes: Array      # NoDiskConflict + max-volume-count family


def mask_components(
    tables: ClusterTables, cyc: CycleArrays, pods: PodArrays
) -> MaskComponents:
    """Decomposed feasibility against the initial state, vmapped over pods."""
    state = initial_state(tables, cyc)
    nodes, classes, terms = tables.nodes, tables.classes, tables.terms
    D = cyc.D
    table = state_affinity_table(tables, cyc, state, pods.valid.shape[0])
    spread = state_spread_counts(tables, cyc, state, pods.valid.shape[0])

    def row(c, nnr, v, pin):
        req_vec = tables.reqs.vec[classes.rid[c]]
        fit = fit_row(req_vec, state.used, nodes.alloc, nodes.valid)
        ps = classes.portset[c]
        psafe = jnp.maximum(ps, 0)
        conflict = port_conflict_row(
            tables.portsets.wild_words[psafe],
            tables.portsets.pair_words[psafe],
            tables.portsets.trip_words[psafe],
            state.ppa, state.ppw, state.ppt,
        )
        port_ok = (ps < 0) | ~conflict
        aff_ok, anti_ok = affinity_rows(
            c, classes, terms, cyc.TM, state.CNT, state.HOLD, nodes, D, table,
            cyc.SAME)
        spread_ok = spread_row(
            c, classes, terms, cyc.TM, state.CNT, cyc.ELN,
            cyc.static.node_match[c], nodes, D, cyc.SAME, spread,
        )
        host_ok = (nnr < 0) | (nodes.name_id == nnr)
        vol_ok = volume_ok_row(tables, state.vol_any, state.vol_rw,
                               state.vol_cnt, c)
        nm = cyc.static.node_match[c]
        # static.mask = node_match ∧ taint_ok ∧ unsched_pass ∧ class valid;
        # recover the taint/unschedulable part by division
        taints_ok = cyc.static.mask[c] | ~nm
        # the pin is a node affinity term: MatchNodeSelector's to refuse
        nm = nm & ((pin < 0) | (nodes.name_id == pin))
        return (nm & v, taints_ok, fit, port_ok, aff_ok, anti_ok, spread_ok,
                host_ok, vol_ok)

    parts = jax.vmap(row)(pods.cls, pods.node_name_req, pods.valid,
                          pods.pin)
    return MaskComponents(*parts)


# --------------------------------------------------------------------------- #
# decision provenance (ISSUE 10): per-pod unschedulability attribution and
# winning-score decomposition as cheap sum-reductions over the SAME mask/score
# expression trees the engines evaluate — computed inside the wave dispatch
# when KTPU_EXPLAIN is on, byte-for-byte absent otherwise (a static jit flag).
# --------------------------------------------------------------------------- #

#: predicate order of ExplainResult.reasons — kube PredicateFailureReason
#: names rendered by sched/explain.py (algorithm/predicates/error.go)
EXPLAIN_PREDICATES = ("node_match", "taints", "fit", "ports", "affinity",
                      "anti", "spread", "host", "volumes")
#: score-component order of ExplainResult.score_parts (prioritizeNodes'
#: weighted sum, decomposed)
EXPLAIN_SCORE_COMPONENTS = ("static", "least", "balanced", "most",
                            "interpod", "even", "ssel", "rtc")
#: candidate nodes reported per pod (clamped to N at trace time)
EXPLAIN_TOPK = 3


class ExplainResult(NamedTuple):
    """Per-pod decision attribution for one wave, evaluated against the
    POST-wave assume state (result.state): the "why is this pod still
    pending NOW" answer, not a replay of each scan step. All counts are
    over VALID nodes; invalid (padding) pods zero out."""

    reasons: Array         # [P, 9] i32 — nodes rejected per predicate
    valid_nodes: Array     # [P] i32 — denominator ("0/N nodes are available")
    feasible_nodes: Array  # [P] i32 — nodes passing EVERY predicate
    rejected_any: Array    # [P] i32 — valid_nodes - feasible_nodes
    top_nodes: Array       # [P, K] i32 — best feasible nodes by score (-1 pad)
    top_scores: Array      # [P, K] f32
    score_parts: Array     # [P, 8] f32 — component breakdown at part_node
    part_node: Array       # [P] i32 — chosen node if scheduled, else best
    #                        feasible node, else -1


def _explain_mask_row(tables: ClusterTables, cyc: CycleArrays,
                      state: AssignState, c: Array,
                      table: TermCounts | None = None,
                      spread: SpreadCounts | None = None):
    """The cheap half of attribution for ONE class against `state`: the 8
    class-granular predicate planes reduced to rejected-node counts
    (host/spec.nodeName is per-pod and folded by the caller) plus the
    full-mask [N] row. Every plane honors its EngineConfig plugin flag
    exactly as pod_mask_row/mask_dynamic_row compose it — a disabled
    plugin never rejects, so counts reconcile with the engine's own
    verdicts. This half runs on EVERY explain-on wave (sub-ms at bench
    shapes)."""
    from .lattice import _on

    nodes, classes, terms = tables.nodes, tables.classes, tables.terms
    ecfg = cyc.ecfg
    D = cyc.D
    nm = cyc.static.node_match[c]
    # static.mask = node_match ∧ taint_ok ∧ unsched_pass ∧ class-valid;
    # recover the taint/unschedulable plane by division (mask_components)
    taints_ok = cyc.static.mask[c] | ~nm
    # dynamic planes through the SAME helpers mask_dynamic_row conjoins —
    # the engines' verdicts and these counts cannot drift apart
    fit = fit_plane(tables, cyc, c, state.used)
    ports_ok = ports_plane(tables, cyc, c, state.ppa, state.ppw, state.ppt)
    vol_ok = volumes_plane(tables, cyc, c, state.vol_any, state.vol_rw,
                           state.vol_cnt)
    # interpod/spread decomposed: mask_context_row conjoins (aff ∧ anti)
    # under one flag — KEEP the flag composition in sync with it
    aff_ok, anti_ok = affinity_rows(
        c, classes, terms, cyc.TM, state.CNT, state.HOLD, nodes, D, table,
        cyc.SAME)
    aff_ok = aff_ok | ~_on(ecfg.f_interpod)
    anti_ok = anti_ok | ~_on(ecfg.f_interpod)
    spread_ok = spread_row(
        c, classes, terms, cyc.TM, state.CNT, cyc.ELN,
        cyc.static.node_match[c], nodes, D, cyc.SAME, spread,
    ) | ~_on(ecfg.f_spread)
    planes = jnp.stack([nm, taints_ok, fit, ports_ok, aff_ok, anti_ok,
                        spread_ok, vol_ok])            # [8, N]
    nv = nodes.valid
    reasons8 = jnp.sum(nv[None, :] & ~planes, axis=1).astype(jnp.int32)
    mask8 = planes.all(axis=0) & nv
    return reasons8, mask8


def _explain_score_row(tables: ClusterTables, cyc: CycleArrays,
                       state: AssignState, c: Array,
                       table: TermCounts | None = None,
                       spread: SpreadCounts | None = None):
    """The EXPENSIVE half for one class: the composed score row and the
    context score components (soft inter-pod affinity's min/max
    normalization, even-spread, selector-spread — one extra full score
    pass per class, ~an engine wave-iteration's worth of work). Only
    evaluated under the failure-gated branch of explain_assignments."""
    ctxs = score_context_row(tables, cyc, state, c, table, spread)
    ctx = jnp.stack([ctxs.soft_ip, ctxs.even_soft, ctxs.ssel])  # [3, N]
    score = score_combine_row(tables, cyc, c, state.used, ctxs)
    return score, ctx


def _row_topk(masked, K: int):
    """Top-K (node index, score) of one masked score row — K iterative
    argmax passes with where-iota elimination, NOT lax.top_k: top_k sorts
    the whole row (N log N per row — measured as the bulk of the
    attribution overhead at bench shapes) while K=3 linear maxes keep the
    engines' own argmax tie-break (lowest index wins). Dead slots (score
    -inf: fewer than K feasible nodes) report node -1 / score 0."""
    iota = jnp.arange(masked.shape[0], dtype=jnp.int32)
    tops_l, topi_l = [], []
    cur = masked
    for _ in range(K):
        i = jnp.argmax(cur).astype(jnp.int32)
        tops_l.append(cur[i])
        topi_l.append(i)
        cur = jnp.where(iota == i, -jnp.inf, cur)
    tops = jnp.stack(tops_l)
    topi = jnp.stack(topi_l)
    live = tops > -jnp.inf
    return jnp.where(live, topi, -1), jnp.where(live, tops, 0.0)


def explain_assignments(
    tables: ClusterTables, cyc: CycleArrays, pods: PodArrays,
    result: AssignResult, granularity: str = "class",
) -> ExplainResult:
    """The attribution reduction for one wave, against result.state (the
    post-wave assume state). Two granularities, bit-equal by shared code:

      * "pod"   — the spec: one full row per pod (the scan engine's
                  granularity; cost scales with P·N).
      * "class" — the cheap half evaluates ONCE per interned class (the
                  waves engine already thinks in [SC, N] planes), then per-pod
                  work is pure GATHERS when no pod of the batch names a node
                  (spec.nodeName, or a pin: `PodArrays.pin`; a lax.cond
                  keeps the per-pod fold for batches that do).

    Cost discipline (the <=2% bench budget): the REASON/feasibility
    reductions (the mask planes) always run — they are sum-reductions
    over planes the lattice already materializes, sub-ms. The score
    DECOMPOSITION — candidate ranking and per-component parts, which
    needs one extra full score-context pass per class (an engine
    wave-iteration's worth of work) — runs under a failure-gated
    lax.cond: a wave with nothing to explain (every pod placed) skips
    it, reporting empty candidates and zeroed parts; any wave carrying
    an unschedulable pod pays the full cost, proportional to need.

    Both granularities share `_explain_mask_row`/`_explain_score_row`/
    `_row_topk`/the parts stage, so the outputs are bit-equal — asserted
    by tests/test_explain.py."""
    from .lattice import _on

    state = result.state
    chosen = result.node
    nodes = tables.nodes
    nv = nodes.valid
    SC = tables.classes.valid.shape[0]
    P = pods.valid.shape[0]
    K = min(EXPLAIN_TOPK, int(nv.shape[0]))
    cls_safe = jnp.clip(pods.cls, 0, SC - 1)
    validn_scalar = jnp.sum(nv).astype(jnp.int32)
    i32 = jnp.int32
    any_failed = ((chosen < 0) & pods.valid).any()
    rows = P if granularity == "pod" else SC
    # the state's two aggregates, each where its rule says a table pays
    agg = (state_affinity_table(tables, cyc, state, rows),
           state_spread_counts(tables, cyc, state, rows))

    def host_plane(nnr, pin):
        """spec.nodeName's plane and the pin's (MatchNodeSelector refuses by
        the latter: `fold` counts it there)."""
        return ((nnr < 0) | (nodes.name_id == nnr) | ~_on(cyc.ecfg.f_name),
                pin_plane(tables, cyc, pin))

    def fold(c, r8, m8, nnr, pin):
        """One pod's reasons [9] and feasible-node count from its class's
        (`r8`, `m8`) and its own two name constraints."""
        host_ok, pin_ok = host_plane(nnr, pin)
        host_rej = jnp.sum(nv & ~host_ok).astype(i32)
        nm_rej = jnp.where(
            pin >= 0,
            jnp.sum(nv & ~(cyc.static.node_match[c] & pin_ok)).astype(i32),
            r8[0])
        reasons = jnp.concatenate(
            [nm_rej[None], r8[1:7], host_rej[None], r8[7:]])
        return reasons, jnp.sum(m8 & host_ok & pin_ok).astype(i32)

    def parts_stage(pn, ctx_at):
        """Score decomposition at the explained node: [P]-sized gathers +
        pointwise resource scores (shared by both granularities)."""
        w = cyc.ecfg
        j = jnp.maximum(pn, 0)
        req = tables.reqs.vec[tables.classes.rid[cls_safe]]  # [P, R]
        least, balanced, most = jax.vmap(resource_scores_row)(
            req, state.used[j][:, None, :], nodes.alloc[j][:, None, :])
        rtc = jax.vmap(
            lambda q, u, a: rtc_score_row(q, u, a, w.rtc_x, w.rtc_y, w.rtc_w)
        )(req, state.used[j][:, None, :], nodes.alloc[j][:, None, :])
        parts = jnp.stack([
            cyc.static.score[cls_safe, j],
            least[:, 0] * w.w_least, balanced[:, 0] * w.w_balanced,
            most[:, 0] * w.w_most,
            ctx_at[:, 0] * w.w_interpod, ctx_at[:, 1] * w.w_even,
            ctx_at[:, 2] * w.w_ssel, rtc[:, 0] * w.w_rtc,
        ], axis=1)                                           # [P, 8]
        return jnp.where((pn >= 0)[:, None], parts, 0.0)

    def cheap_score(_):
        # failure-free wave: nothing to rank or decompose
        return (jnp.full((P, K), -1, i32), jnp.zeros((P, K), jnp.float32),
                jnp.zeros((P, len(EXPLAIN_SCORE_COMPONENTS)), jnp.float32),
                jnp.where(chosen >= 0, chosen, -1))

    if granularity == "pod":
        def mrow(c, nnr, pin):
            r8, m8 = _explain_mask_row(tables, cyc, state, c, *agg)
            return fold(c, r8, m8, nnr, pin)

        reasons, feas = jax.vmap(mrow)(cls_safe, pods.node_name_req,
                                       pods.pin)

        def pod_score(_):
            def row(c, nnr, pin, ch):
                _r8, m8 = _explain_mask_row(tables, cyc, state, c, *agg)
                host_ok, pin_ok = host_plane(nnr, pin)
                full = m8 & host_ok & pin_ok
                sc_row, cx = _explain_score_row(tables, cyc, state, c, *agg)
                topn, tops = _row_topk(
                    jnp.where(full, sc_row, -jnp.inf), K)
                pn = jnp.where(ch >= 0, ch, topn[0])
                ctx_at = cx[:, jnp.maximum(pn, 0)]
                return topn, tops, pn, ctx_at

            topn, tops, pn, ctx_at = jax.vmap(row)(
                cls_safe, pods.node_name_req, pods.pin, chosen)
            return topn, tops, parts_stage(pn, ctx_at), pn

        topn, tops, parts, pn = jax.lax.cond(
            any_failed, pod_score, cheap_score, None)
    else:
        r8, m8 = jax.vmap(
            lambda c: _explain_mask_row(tables, cyc, state, c, *agg)
        )(jnp.arange(SC, dtype=jnp.int32))
        reasons9_c = jnp.concatenate(
            [r8[:, :7], jnp.zeros((SC, 1), i32), r8[:, 7:]], axis=1)
        feas_c = m8.sum(axis=1).astype(i32)
        any_pinned = (((pods.node_name_req >= 0) | (pods.pin >= 0))
                      & pods.valid).any()

        def gather_mask(_):
            # no pod names a node: both planes are all-true for every pod,
            # so the class-level reductions ARE the per-pod answers
            return reasons9_c[cls_safe], feas_c[cls_safe]

        def host_mask(_):
            return jax.vmap(lambda c, nnr, pin: fold(c, r8[c], m8[c], nnr,
                                                     pin))(
                cls_safe, pods.node_name_req, pods.pin)

        reasons, feas = jax.lax.cond(any_pinned, host_mask, gather_mask,
                                     None)

        def class_score(_):
            sc_rows, cx = jax.vmap(
                lambda c: _explain_score_row(tables, cyc, state, c, *agg)
            )(jnp.arange(SC, dtype=jnp.int32))
            masked_c = jnp.where(m8, sc_rows, -jnp.inf)
            topn_c, tops_c = jax.vmap(
                lambda row: _row_topk(row, K))(masked_c)

            def g(_):
                return topn_c[cls_safe], tops_c[cls_safe]

            def h(_):
                def fin(c, nnr, pin):
                    host_ok, pin_ok = host_plane(nnr, pin)
                    return _row_topk(jnp.where(m8[c] & host_ok & pin_ok,
                                               sc_rows[c], -jnp.inf), K)

                return jax.vmap(fin)(cls_safe, pods.node_name_req, pods.pin)

            topn, tops = jax.lax.cond(any_pinned, h, g, None)
            pn = jnp.where(chosen >= 0, chosen, topn[:, 0])
            ctx_at = cx[cls_safe, :, jnp.maximum(pn, 0)]
            return topn, tops, parts_stage(pn, ctx_at), pn

        topn, tops, parts, pn = jax.lax.cond(
            any_failed, class_score, cheap_score, None)

    # invalid (padding) pods zero out across the board
    v = pods.valid
    vi = v.astype(i32)
    return ExplainResult(
        reasons=reasons * vi[:, None],
        valid_nodes=validn_scalar * vi,
        feasible_nodes=feas * vi,
        rejected_any=(validn_scalar - feas) * vi,
        top_nodes=jnp.where(v[:, None], topn, -1),
        top_scores=tops * v[:, None].astype(jnp.float32),
        score_parts=parts * v[:, None].astype(jnp.float32),
        part_node=jnp.where(v, pn, -1),
    )


def score_matrix(
    tables: ClusterTables, cyc: CycleArrays, pods: PodArrays
) -> Array:
    """[P, N] Score for every pending pod against the *initial* state — the
    tensor analog of prioritizeNodes (generic_scheduler.go:714-869): static
    lattice scores (preferred node affinity, taint PreferNoSchedule) plus
    least-requested/balanced-allocation plus soft inter-pod affinity, all
    weight-1 summed. Infeasible nodes score -inf."""
    state = initial_state(tables, cyc)
    table = state_affinity_table(tables, cyc, state, pods.valid.shape[0])
    spread = state_spread_counts(tables, cyc, state, pods.valid.shape[0])

    def row(c, nnr, v, pin):
        mask = pod_mask_row(tables, cyc, state, c, nnr, v, table, spread, pin)
        return jnp.where(
            mask, score_row(tables, cyc, state, c, table, spread), -jnp.inf)

    return jax.vmap(row)(pods.cls, pods.node_name_req, pods.valid, pods.pin)


def initial_state(tables: ClusterTables, cyc: CycleArrays) -> AssignState:
    n = tables.nodes
    return AssignState(
        used=n.used, ppa=n.port_pair_any, ppw=n.port_pair_wild, ppt=n.port_triple,
        CNT=cyc.CNT, HOLD=cyc.HOLD, WSYM=cyc.WSYM,
        vol_any=n.vol_any, vol_rw=n.vol_rw, vol_cnt=n.vol_cnt,
    )
