"""Topology spread as the parent of PR 43 (commit 23cbac3) computed it, kept
verbatim as the plain reference the new forms are held to bit for bit
(tests/test_scores.py, tests/test_waves.py): the eligible-DOMAIN table
(`ops/topospread.py eligible_domains`, a scatter-max into [SC, TS, D + 1]),
the Filter row and the soft score each with its own `domain_agg` scatter-add
(with the `eligible` mask that function then took) and gather back, and the
waves round's quota rows with a third. Only the closures' names became
arguments."""

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops.interpod import domain_of_term

MAX_NODE_SCORE = 100.0
_I32_MAX = int(jnp.iinfo(jnp.int32).max)


def domain_agg(cnt_rows, dom, D, eligible=None):
    """Aggregate per-node counts over topology domains → [A, D+1] (slot D is
    the discard bucket). Optionally restrict to eligible nodes (spread)."""
    vals = cnt_rows
    if eligible is not None:
        vals = jnp.where(eligible, vals, 0)
    idx = jnp.where(dom >= 0, dom, D)
    A = vals.shape[0]
    seg = jnp.zeros((A, D + 1), vals.dtype)
    return seg.at[jnp.arange(A)[:, None], idx].add(vals)


def eligible_domains(node_match, classes, nodes, D):
    """ELD [SC, TS, D+1] bool: domains (of each constraint's key) containing at
    least one node eligible for the class (metadata.go:145-151's node filter)."""
    SC, TS = classes.tsc_key.shape
    k = jnp.maximum(classes.tsc_key, 0)          # [SC, TS]
    dom = nodes.domain[:, k]                      # [N, SC, TS]
    ok = (
        node_match.T[:, :, None]
        & (dom >= 0)
        & (classes.tsc_key >= 0)[None, :, :]
        & nodes.valid[:, None, None]
    )  # [N, SC, TS]
    idx = jnp.where(ok, dom, D)
    eld = jnp.zeros((SC, TS, D + 1), bool)
    return eld.at[
        jnp.arange(SC)[None, :, None], jnp.arange(TS)[None, None, :], idx
    ].max(ok)


def spread_row(cls, classes, terms, TM, CNT_node, ELD, node_match_row, nodes,
               D):
    """[N] bool: all hard spread constraints satisfied on each node."""
    s_ids = classes.tsc_term[cls]      # [TS]
    s = jnp.maximum(s_ids, 0)
    hard = classes.tsc_hard[cls] & (s_ids >= 0)  # [TS]
    skew_max = classes.tsc_maxskew[cls]

    dom, has_key = domain_of_term(nodes, terms.topo_key[s])  # [TS, N]
    # counts restricted to nodes eligible for this pod (metadata.go:145-151)
    seg = domain_agg(CNT_node[s], dom, D, eligible=node_match_row[None, :])  # [TS, D+1]
    cnt = jnp.take_along_axis(seg, jnp.where(dom >= 0, dom, D), axis=1)     # [TS, N]

    eld = ELD[cls]  # [TS, D+1]
    any_eligible = eld[:, :D].any(-1)  # [TS]
    min_cnt = jnp.min(
        jnp.where(eld[:, :D], seg[:, :D], jnp.iinfo(jnp.int32).max), axis=-1
    )  # [TS]
    self_match = TM[s, cls]  # [TS] — constraint selector vs own labels

    skew = cnt + self_match[:, None].astype(jnp.int32) - min_cnt[:, None]
    ok = has_key & (skew <= skew_max[:, None])
    # empty eligible-domain map ⇒ constraint passes everywhere (:1661-1663)
    per_constraint = jnp.where(
        (hard & any_eligible)[:, None], ok, jnp.ones_like(ok)
    )
    return per_constraint.all(0)


def even_spread_soft_row(cls, classes, terms, CNT, nodes, node_match_row, D):
    s_ids = classes.tsc_term[cls]                 # [TS]
    s = jnp.maximum(s_ids, 0)
    soft = (s_ids >= 0) & ~classes.tsc_hard[cls]  # [TS]

    dom, has_key = domain_of_term(nodes, terms.topo_key[s])  # [TS, N]
    # counts restricted to nodes eligible for this pod (buildPodTopologySpreadMap
    # checks PodMatchesNodeSelectorAndAffinityTerms on the counted node)
    seg = domain_agg(CNT[s], dom, D, eligible=node_match_row[None, :])
    cnt = jnp.take_along_axis(seg, jnp.where(dom >= 0, dom, D), axis=1)
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


def spread_quota_rows(tables, cyc_static_node_match, TM, ELD, CNT, D,
                      neg_score, rot_pos, offs):
    """[SC, TS, N] bool: `_domain_quota_pass.apply_spread`'s rows (ops/waves.py
    at 23cbac3 :249-271): the domain's cap from a [D + 1] table, said of each
    node by a gather in node order."""
    from kubernetes_tpu.ops.waves import _within_quota

    classes, nodes, terms = tables.classes, tables.nodes, tables.terms
    SC, TS = classes.tsc_term.shape

    def key_domain(topo_key):
        return domain_of_term(nodes, topo_key[None])[0][0]

    def slot_quota(c, dom, active, quota_n):
        return ~active | _within_quota(neg_score[c], rot_pos[c], offs[c],
                                       dom, D, quota_n)

    def spread_slot(c, t):
        s_id = classes.tsc_term[c, t]
        s = jnp.maximum(s_id, 0)
        active = (
            (s_id >= 0) & classes.tsc_hard[c, t] & TM[s, c]
        )
        eld = ELD[c, t, :D]
        active = active & eld.any()
        dom = key_domain(terms.topo_key[s])
        seg = domain_agg(CNT[s][None], dom[None], D,
                         eligible=cyc_static_node_match[c][None])[0]
        min_cnt = jnp.min(jnp.where(eld, seg[:D], _I32_MAX))
        quota = jnp.clip(
            classes.tsc_maxskew[c, t] + min_cnt - seg, 0, _I32_MAX
        )
        # the domain's cap is said of each node ONCE, here in node order
        return slot_quota(c, dom, active, quota[jnp.where(dom >= 0, dom, D)])

    return jax.vmap(
        lambda c: jax.vmap(lambda t: spread_slot(c, t))(
            jnp.arange(TS, dtype=jnp.int32))
    )(jnp.arange(SC, dtype=jnp.int32))        # [SC, TS, N]
