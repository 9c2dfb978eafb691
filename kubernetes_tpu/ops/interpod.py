"""Inter-pod affinity/anti-affinity as tensor ops over interned terms.

The reference's approach (predicates.go:1212-1520 + metadata.go:60-112) builds,
per incoming pod, maps (topoKey, topoValue) → matching existing pods by scanning
all pods × all terms with 16-way goroutine fan-out. The TPU re-design exploits
two quotients:

  1. terms are interned (TermTable) — each distinct (selector, namespaces,
     topologyKey) is evaluated once per cycle, not once per pod;
  2. matching is factored through label-set classes: TM[S, SC] says "term s
     matches pod-class c".

Live state is carried as per-NODE counts (CNT_node[S, N]: matching pods of term
s on node n; HOLD_node[S, N]: holders of anti-term s on node n) and aggregated
over topology domains on demand — because different consumers aggregate
differently: inter-pod affinity counts pods on ALL nodes carrying the key
(metadata.go:407-437 has no node filter), while topology spread counts only
pods on nodes *eligible* for the incoming pod (metadata.go:145-151). Keeping the
node axis as the source of truth makes both exact.

The inter-pod aggregate of term s over the domains of term s's own key depends
on s and the state alone, never on the class that asks. `in_domain_counts` is
that aggregate in one of two parameterisations, chosen from static shapes at
trace time (ops/assign.py state_affinity_table, by state/dims.py
affinity_agg): a caller that evaluates many classes against ONE state (the
waves round over SC classes) asks it once for every term —
`term_domain_counts`, the [S, N] table, which sums HOLD and WSYM in the same
pass — and every class SELECTS its slots' rows ("term"); a caller with fewer
rows x slots than terms (a verb's P pods, a what-if lane's one preemptor, a
scan step) asks it for its own slots ("row"). Integer counts either way: the
two are bit-equal.

HOW a per-node table is summed over each node's domain (`in_domain_sums`) is
a second static choice, state/dims.py domain_sum, from N and K alone:

  * "product": row s times SAME_k, the 0/1 matrix "nodes m and n carry key k
    and share its value" (`same_domain`, built once a cycle by
    ops/lattice.py build_cycle and carried on CycleArrays.SAME, outside the
    rounds' loop). One bf16 product on the MXU for every table that shares
    the keys; the integers split into 8-bit digits so every factor is exact
    in bf16 and every f32 partial sum an integer below 2^24. No scatter, no
    gather;
  * "scatter": one scatter-add into [A, D+1], one gather back to [A, N] —
    serial in its A x N updates (~19 ns each on the TPU, and ~10 ns an
    element gathered back), kept for node axes whose K x N x N matrix has no
    room beside the state.

The two are bit-equal (tests/test_scores.py). Topology spread's counts go
through the same function and the same choice: a constraint's CNT row zeroed
on the nodes the class is not eligible for, summed over each node's domain
(ops/topospread.py spread_counts, one sum of SC x TS rows a round), and the
0/1 eligibility rows themselves once a cycle (eligible_in_domain). The
minimum over a key's eligible DOMAINS that spread reads is the minimum over
the nodes whose domain is eligible, so nothing is kept per domain.

The predicate semantics (satisfiesPodsAffinityAntiAffinity :1421-1520):
  * affinity:  ∀ term: node-has-key ∧ domain-count > 0, with the first-pod
    escape (:1436-1440): total potential matches == 0 ∧ pod matches its own
    terms ⇒ pass on every node;
  * anti-affinity: ∄ term with count > 0 in-domain;
  * existing-pod symmetry (:1319-1360): node blocked iff some anti-term matches
    the incoming pod and has a holder in the node's domain.

CNT_node/HOLD_node live in the assignment scan's carry so pods placed earlier in
the cycle are visible to later pods — the device analog of the assume cache
(scheduler.go:676, cache.go:283).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..state.arrays import (
    Array,
    LabelSetTable,
    NodeArrays,
    PodArrays,
    PodClassTable,
    TermTable,
)
from .labels import ns_bit, term_labelset_matrix


def term_class_matrix(
    terms: TermTable, labelsets: LabelSetTable, classes: PodClassTable
) -> Array:
    """TM [S, SC] bool: term s (selector ∧ namespaces) matches pod-class c."""
    M = term_labelset_matrix(terms, labelsets)  # [S, SL]
    sel = jnp.take_along_axis(
        M, jnp.maximum(classes.labelset, 0)[None, :], axis=1
    )  # [S, SC]
    nsok = ns_bit(terms.ns_words[:, None, :], classes.ns[None, :])  # [S, SC]
    return sel & nsok & classes.valid[None, :] & terms.valid[:, None]


def class_term_membership(term_ids: Array, S: int) -> Array:
    """[SC, A] term-id slots → [SC, S] multi-hot membership (-1 pads dropped)."""
    ids = term_ids
    hot = (ids[..., None] == jnp.arange(S)[None, None, :]) & (ids[..., None] >= 0)
    return hot.any(axis=1)  # [SC, S]


def class_node_hist(pods: PodArrays, SC: int, N: int) -> Array:
    """M [SC, N] i32: how many valid existing pods of class c sit (bound or
    assumed) on node n. An existing pod enters every per-node seed only
    through (cls, node_id), so this one scatter-add of E ones — column N the
    discard slot for unbound / invalid rows, sliced off — is all that reads
    the E axis; per_node_counts and scores.weighted_per_node are products
    against it. A row with cls -1 counts under class 0, as every class gather
    of an existing pod does (preempt.py's cls_e)."""
    on_node = (pods.node_id >= 0) & pods.valid
    idx = jnp.where(on_node, pods.node_id, N)
    with jax.named_scope("class_node_hist"):
        M = jnp.zeros((SC, N + 1), jnp.int32).at[
            jnp.maximum(pods.cls, 0), idx].add(1)
    return M[:, :N]


def per_node_counts(TM_or_membership: Array, M: Array) -> Array:
    """[S, SC] bool (term matches class) × M [SC, N] (class_node_hist) →
    [S, N] i32: matching existing pods per node — the node-axis source of
    truth for all domain aggregations. An integer dot, so exact whatever the
    chip's default matmul precision (bf16, wrong above 256) would make of
    a float one."""
    return jnp.dot(TM_or_membership.astype(jnp.int32), M,
                   preferred_element_type=jnp.int32)


def domain_of_term(nodes: NodeArrays, topo_key: Array) -> tuple[Array, Array]:
    """topo_key: [S] → (dom [S, N] compact domain index with -1 absent,
    has_key [S, N])."""
    k = jnp.maximum(topo_key, 0)
    dom = nodes.domain[:, k].T  # [S, N]
    dom = jnp.where((topo_key[:, None] >= 0) & nodes.valid[None, :], dom, -1)
    return dom, dom >= 0


def domain_agg(
    cnt_rows: Array,   # [A, N] per-node counts for A terms
    dom: Array,        # [A, N] compact domain index (-1 absent)
    D: int,
) -> Array:
    """Aggregate per-node counts over topology domains → [A, D+1] (slot D is
    the discard bucket)."""
    idx = jnp.where(dom >= 0, dom, D)
    A = cnt_rows.shape[0]
    seg = jnp.zeros((A, D + 1), cnt_rows.dtype)
    return seg.at[jnp.arange(A)[:, None], idx].add(cnt_rows)


class TermCounts(NamedTuple):
    """What pod (anti-)affinity reads of one state, once per TERM."""

    cnt: Array  # [S, N] i32: pods matching term s in node n's domain of
    #             term s's key; 0 where n lacks the key
    tot: Array  # [S] i32: pods matching term s on nodes carrying the key
    # the state's other two per-term tables, summed in the same pass
    hold: Array  # [S, N] i32: holders of anti-term s in node n's domain
    sym: Array   # [S, N] f32: symmetric weights (scores.py WSYM) likewise


def same_domain(nodes: NodeArrays) -> Array:
    """SAME [K, N, N] bf16 0/1: valid nodes m and n both carry key k and
    share its value. A function of the node table alone — not of the state,
    the round, the class or the table being summed."""
    dom = jnp.where(nodes.valid[:, None], nodes.domain, -1).T    # [K, N]
    same = (dom[:, :, None] == dom[:, None, :]) & (dom[:, :, None] >= 0)
    return same.astype(jnp.bfloat16)


def _same_domain_product(rows: Array, topo_key: Array, same: Array) -> Array:
    """rows [A, N] integer-valued, |value| <= 2^24; topo_key [A]; same
    [K, N, N] → out[a, n] = Σ_m rows[a, m] · same[topo_key[a], m, n], exact.
    The values go in as three 8-bit digits (the top one signed), each exact
    in bf16's 8 bits; a digit's sum over a domain is at most 256 · N, an
    integer below 2^24 for any N `domain_sum` lets through, so the MXU's f32
    accumulation is exact in any order; the digits meet again in i32. One
    product contracts (key, node): a row is laid out under its own key."""
    K = same.shape[0]
    v = rows.astype(jnp.int32)
    digits = jnp.stack([v & 255, (v >> 8) & 255, v >> 16]) \
        .astype(jnp.bfloat16)                                     # [3, A, N]
    own_key = topo_key[:, None] == jnp.arange(K)[None, :]         # [A, K]
    lhs = jnp.where(own_key[None, :, :, None], digits[:, :, None, :], 0)
    out = jax.lax.dot_general(
        lhs, same, (((2, 3), (0, 1)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)     # [3, A, N]
    return ((out[2] << 16) + (out[1] << 8) + out[0]).astype(rows.dtype)


def in_domain_sums(rows: Array, topo_key: Array, nodes: NodeArrays, D: int,
                   same: Array | None = None) -> Array:
    """rows [A, N]: a per-node table of A terms with keys topo_key [A] →
    [A, N]: the table summed over each node's domain of the term's key, 0
    where the node lacks the key. With `same` (CycleArrays.SAME: the
    program's Dims chose "product") a product on the MXU; without, one
    scatter-add into [A, D+1] and one gather back. Bit-equal: the rows are
    integers (i32 counts, integer-valued f32 weights) with sums below
    2^24."""
    if same is not None:
        return _same_domain_product(rows, topo_key, same)
    dom, has_key = domain_of_term(nodes, topo_key)           # [A, N]
    seg = domain_agg(rows, dom, D)                           # [A, D+1]
    out = jnp.take_along_axis(seg, jnp.where(has_key, dom, D), axis=1)
    return jnp.where(has_key, out, 0)


def _on_keyed_nodes(rows: Array, topo_key: Array, nodes: NodeArrays) -> Array:
    """rows [A, N] → [A]: each term's total over the nodes carrying its key."""
    _, has_key = domain_of_term(nodes, topo_key)             # [A, N]
    return jnp.sum(jnp.where(has_key, rows, 0), axis=1)


def term_domain_counts(
    terms: TermTable, CNT_node: Array, HOLD_node: Array, WSYM: Array,
    nodes: NodeArrays, D: int, same: Array | None = None,
) -> TermCounts:
    """The table: every term of the state aggregated once, and the state's
    HOLD and WSYM with it — three [S, N] tables over the same keys are ONE
    [3 S, N] sum (the weights are integer-valued f32: scores.py
    weighted_per_node)."""
    S = CNT_node.shape[0]
    with jax.named_scope("term_domain_counts"):
        stacked = jnp.concatenate(
            [CNT_node, HOLD_node, WSYM.astype(jnp.int32)])
        out = in_domain_sums(stacked, jnp.tile(terms.topo_key, 3), nodes, D,
                             same)
        return TermCounts(
            cnt=out[:S], tot=_on_keyed_nodes(CNT_node, terms.topo_key, nodes),
            hold=out[S:2 * S], sym=out[2 * S:].astype(WSYM.dtype))


def in_domain_counts(
    term_slots: Array,       # [A] term ids, -1 pads read term 0
    terms: TermTable,
    CNT_node: Array,         # [S, N]
    nodes: NodeArrays,
    D: int,
    table: TermCounts | None = None,
    same: Array | None = None,         # CycleArrays.SAME
) -> tuple[Array, Array]:
    """(cnt [A, N], tot [A]) for the terms in `term_slots`: their rows of
    `table` where the caller built one for this state, else aggregated here
    from the slots' own CNT rows."""
    s = jnp.maximum(term_slots, 0)
    if table is not None:
        return table.cnt[s], table.tot[s]
    rows, keys = CNT_node[s], terms.topo_key[s]
    return (in_domain_sums(rows, keys, nodes, D, same),
            _on_keyed_nodes(rows, keys, nodes))


def affinity_rows(
    cls: Array,              # scalar class id
    classes: PodClassTable,
    terms: TermTable,
    TM: Array,               # [S, SC]
    CNT_node: Array,         # [S, N]
    HOLD_node: Array,        # [S, N]
    nodes: NodeArrays,
    D: int,
    table: TermCounts | None = None,   # term_domain_counts of this state
    same: Array | None = None,         # CycleArrays.SAME
) -> tuple[Array, Array]:
    """(affinity_ok [N], anti_ok [N]) for one pod against live counts."""

    # --- required affinity (satisfiesPodsAffinityAntiAffinity :1431-1444) ---
    ats = classes.aff_terms[cls]  # [AT]
    s = jnp.maximum(ats, 0)
    cnt, tot = in_domain_counts(ats, terms, CNT_node, nodes, D, table, same)
    active = ats >= 0
    all_terms = (~active[:, None] | (cnt > 0)).all(0)  # [N]
    total = jnp.sum(jnp.where(active, tot, 0))
    self_all = (~active | TM[s, cls]).all()
    escape = (total == 0) & self_all
    has_any = active.any()
    aff_ok = ~has_any | all_terms | escape

    # --- incoming pod's anti-affinity (nodeMatchesAnyTopologyTerm :1447-1456) ---
    ans = classes.anti_terms[cls]  # [AN]
    cnt_a, _ = in_domain_counts(ans, terms, CNT_node, nodes, D, table, same)
    blocked_own = ((ans >= 0)[:, None] & (cnt_a > 0)).any(0)  # [N]

    # --- existing pods' anti-affinity symmetry (:1319-1360) ---
    # per TERM and not per class: the table's, or computed here, once under
    # a vmap over classes
    hold = table.hold if table is not None else in_domain_sums(
        HOLD_node, terms.topo_key, nodes, D, same)           # [S, N]
    blocked_sym = (TM[:, cls][:, None] & (hold > 0)).any(0)  # [N]

    return aff_ok, ~(blocked_own | blocked_sym)


def soft_affinity_row(
    cls: Array,
    classes: PodClassTable,
    terms: TermTable,
    CNT_node: Array,
    nodes: NodeArrays,
    D: int,
    TM: Array | None = None,
    WSYM: Array | None = None,
    table: TermCounts | None = None,   # term_domain_counts of this state
    same: Array | None = None,         # CycleArrays.SAME
) -> Array:
    """Preferred inter-pod (anti)affinity score [N] f32, 0..100 after min/max
    normalization (interpod_affinity.go:119-215). Both directions: the incoming
    pod's preferred terms against existing pods, AND — when TM/WSYM are given —
    the symmetric pass (existing pods' preferred terms and hard-affinity
    symmetric weight matching the incoming pod, :156-185), summed into the raw
    counts before normalization exactly as the reference's single `counts`
    array is."""

    def contrib(term_slots: Array, weights: Array, sign: float) -> Array:
        cnt, _ = in_domain_counts(term_slots, terms, CNT_node, nodes, D, table,
                                  same)
        w = jnp.where(term_slots >= 0, weights, 0).astype(jnp.float32)
        return sign * (w[:, None] * cnt).sum(0)

    raw = contrib(classes.paff_terms[cls], classes.paff_w[cls], 1.0) + contrib(
        classes.panti_terms[cls], classes.panti_w[cls], -1.0
    )
    if TM is not None and WSYM is not None:
        from .scores import sym_affinity_contrib

        sym = table.sym if table is not None else in_domain_sums(
            WSYM, terms.topo_key, nodes, D, same)
        raw = raw + sym_affinity_contrib(cls, TM, sym)
    lo = jnp.min(jnp.where(nodes.valid, raw, jnp.inf))
    hi = jnp.max(jnp.where(nodes.valid, raw, -jnp.inf))
    return jnp.where(hi > lo, 100.0 * (raw - lo) / jnp.maximum(hi - lo, 1e-9), 0.0)
