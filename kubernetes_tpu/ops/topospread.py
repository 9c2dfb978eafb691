"""PodTopologySpread (EvenPodsSpread) as tensor ops.

Reference semantics: EvenPodsSpreadPredicate (predicates.go:1643-1703) with
metadata (metadata.go:114-176): for each hard (DoNotSchedule) constraint,
  skew = matchNum(node's pair) + selfMatch − minMatchNum  must be ≤ maxSkew,
where matchNum counts same-namespace existing pods matching the constraint's
selector in the candidate node's topology domain — counting ONLY pods on nodes
that pass the incoming pod's nodeSelector/node-affinity (metadata.go:145-151
skips ineligible nodes) — and minMatchNum is the minimum over eligible domains
(the 2-slot criticalPaths online-min, metadata.go:78-112). A node lacking the
topology key fails; a pod whose eligible-domain map is empty passes
everywhere (predicates.go:1661-1663).

Constraint selectors are interned as terms with namespaces={pod.namespace}, so
counts come from the same CNT_node[S, N] carry as inter-pod affinity and stay
live as pods land during the assignment scan; eligibility masking happens at
aggregation time per class.

How the count is made (`spread_counts`): the constraint's CNT row, zeroed on
the nodes the class is not eligible for, summed over each node's domain by
`interpod.in_domain_sums` — under the form state/dims.py domain_sum chose for
the program, a product against the cycle's same-domain matrices or ONE
scatter-add and gather. Everything is said of NODES, nothing of domains: a
count is constant on a domain and every eligible domain holds an eligible
node carrying the key, so the minimum over eligible domains is the minimum
over the nodes whose domain is eligible (`eligible_in_domain`, ELN: a
function of the cycle's static tables, built once by ops/lattice.py
build_cycle). One `SpreadCounts` serves the Filter row (`spread_row`), the
ScheduleAnyway score (scores.even_spread_soft_row) and the waves round's
per-node admission cap (waves.py `spread_slot`): a program that evaluates
every class against one state builds it once for all (class, slot) rows
(ops/assign.py state_spread_counts) and the three select their class's rows;
one with few rows (a verb's pods, a what-if lane, a scan step) asks for its
own class's.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..state.arrays import Array, NodeArrays, PodClassTable, TermTable
from .interpod import domain_of_term, in_domain_sums

_I32_MAX = int(jnp.iinfo(jnp.int32).max)


class SpreadCounts(NamedTuple):
    """What topology spread reads of one state, per (class, constraint slot);
    leading axes are the caller's classes ([SC] for a state's table, none for
    one class's own rows)."""

    cnt: Array           # [..., TS, N] i32: pods matching the slot's selector
    #                      on nodes ELIGIBLE for the class in node n's domain
    #                      of the slot's key; 0 where n lacks the key
    min_cnt: Array       # [..., TS] i32: the least such count over eligible
    #                      domains; i32 max where there is none
    any_eligible: Array  # [..., TS] bool: some domain holds an eligible node


def eligible_in_domain(
    node_match: Array,     # [SC, N] — nodeSelector ∧ node-affinity only
    classes: PodClassTable,
    nodes: NodeArrays,
    D: int,
    same: Array | None = None,   # CycleArrays.SAME
) -> Array:
    """ELN [SC, TS, N] bool: node n carries the constraint's key and its
    domain holds at least one node eligible for the class (metadata.go:145-151's
    node filter), n itself eligible or not. False on a slot without a key."""
    SC, TS = classes.tsc_key.shape
    N = node_match.shape[1]
    rows = jnp.broadcast_to(node_match[:, None, :], (SC, TS, N))
    with jax.named_scope("eligible_in_domain"):
        held = in_domain_sums(rows.reshape(SC * TS, N).astype(jnp.int32),
                              classes.tsc_key.reshape(SC * TS), nodes, D, same)
    return (held > 0).reshape(SC, TS, N)


def eligible_domain_counts(
    cls: Array,             # [...] class ids
    classes: PodClassTable,
    terms: TermTable,
    CNT_node: Array,        # [S, N] live per-node match counts
    node_match_rows: Array, # [..., N] — the classes' eligibility rows
    nodes: NodeArrays,
    D: int,
    same: Array | None = None,   # CycleArrays.SAME
) -> Array:
    """[..., TS, N] i32: `SpreadCounts.cnt` for the classes' slots against
    one state — all their rows in ONE in-domain sum."""
    s = jnp.maximum(classes.tsc_term[cls], 0)                 # [..., TS]
    # counts restricted to nodes eligible for this pod (metadata.go:145-151;
    # buildPodTopologySpreadMap checks the counted node likewise)
    rows = jnp.where(node_match_rows[..., None, :], CNT_node[s], 0)
    N = rows.shape[-1]
    with jax.named_scope("spread_counts"):
        cnt = in_domain_sums(rows.reshape(-1, N),
                             terms.topo_key[s].reshape(-1), nodes, D, same)
    return cnt.reshape(rows.shape)


def spread_counts(
    cls: Array,             # [...] class ids
    classes: PodClassTable,
    terms: TermTable,
    CNT_node: Array,        # [S, N]
    node_match_rows: Array, # [..., N]
    eln: Array,             # [..., TS, N] — CycleArrays.ELN of the classes
    nodes: NodeArrays,
    D: int,
    same: Array | None = None,
) -> SpreadCounts:
    """The classes' counts against one state, with the minimum over the
    nodes whose domain is eligible (= over eligible domains) beside them."""
    cnt = eligible_domain_counts(cls, classes, terms, CNT_node,
                                 node_match_rows, nodes, D, same)
    return SpreadCounts(
        cnt=cnt,
        min_cnt=jnp.min(jnp.where(eln, cnt, _I32_MAX), axis=-1),
        any_eligible=eln.any(-1))


def spread_row(
    cls: Array,            # scalar class id
    classes: PodClassTable,
    terms: TermTable,
    TM: Array,             # [S, SC]
    CNT_node: Array,       # [S, N] live per-node match counts
    ELN: Array,            # [SC, TS, N]
    node_match_row: Array, # [N] — this class's nodeSelector/affinity eligibility
    nodes: NodeArrays,
    D: int,
    same: Array | None = None,            # CycleArrays.SAME
    counts: SpreadCounts | None = None,   # the state's, over every class
) -> Array:
    """[N] bool: all hard spread constraints satisfied on each node."""
    s_ids = classes.tsc_term[cls]      # [TS]
    s = jnp.maximum(s_ids, 0)
    hard = classes.tsc_hard[cls] & (s_ids >= 0)  # [TS]
    skew_max = classes.tsc_maxskew[cls]

    own = jax.tree.map(lambda x: x[cls], counts) if counts is not None \
        else spread_counts(cls, classes, terms, CNT_node, node_match_row,
                           ELN[cls], nodes, D, same)
    _, has_key = domain_of_term(nodes, terms.topo_key[s])        # [TS, N]
    self_match = TM[s, cls]  # [TS] — constraint selector vs own labels

    skew = own.cnt + self_match[:, None].astype(jnp.int32) \
        - own.min_cnt[:, None]
    ok = has_key & (skew <= skew_max[:, None])
    # empty eligible-domain map ⇒ constraint passes everywhere (:1661-1663)
    per_constraint = jnp.where(
        (hard & own.any_eligible)[:, None], ok, jnp.ones_like(ok)
    )
    return per_constraint.all(0)
