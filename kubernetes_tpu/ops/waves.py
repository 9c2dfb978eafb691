"""Wave-parallel batched assignment: the scheduling cycle as a fixpoint of
dense [SC, N] evaluations instead of a P-step sequential scan.

The reference schedules one pod at a time (scheduler.go:596-763); ops/assign.py
reproduces that literally as a lax.scan whose 50k serialized steps leave the
TPU idle. This module replaces it as the default path. Per wave:

  1. every pod CLASS still holding pending pods evaluates its full Filter mask
     and Score row against the committed state — one vmapped dense pass over
     [SC, N], the shape the MXU/VPU wants (pods of a class are spec-identical,
     so per-pod rows would be redundant);
  2. admission is cross-tier: queue order (activeQ: priority desc, creation
     asc — internal/queue/scheduling_queue.go:119-138) is enforced where it
     is OBSERVABLE — through the interaction graph (step 4) and the
     rank-ordered contention passes (step 5) — instead of a global
     priority-tier gate, so independent lower-priority classes need not
     wait out higher tiers wave-by-wave;
  3. each admitting class claims up to one pod per node on its top-scored
     feasible nodes, subject to per-domain quotas that make every same-wave
     admission pair NON-INTERFERING:
       - hard topology-spread (predicates.go:1643): at most
         maxSkew + minMatch − count(d) new matching pods per domain d
         (the criticalPaths online-min, metadata.go:78-112, evaluated at
         wave start — conservative, never violating), said of each NODE of
         d from the round's one `SpreadCounts` (ops/assign.py
         state_spread_counts: the count and the minimum the Filter row and
         the soft score read, built once a round outside the class axis);
       - self-matching anti-affinity (predicates.go:1447-1456): one pod per
         domain per wave;
       - required-affinity first-pod escape (predicates.go:1436-1440): a class
         whose terms have zero matches admits exactly one pod, so followers
         co-locate with it next wave;
  4. cross-CLASS term interactions (my anti/spread/affinity term matches your
     pods) are serialized through an [SC, SC] interaction graph: a class
     admits only if no earlier-queued class it interacts with admits in the
     same wave (vectorized independent set — no scan);
  5. same-node contention between classes is resolved in queue order by a
     cumulative resource-sum / port-OR pass; losers retry next wave;
  6. failed runs consume eagerly: a zero-progress wave marks the frozen
     priority run of every attempting class unschedulable (the sequential
     scan's outcome on unchanging state), and a class that is
     Filter-infeasible on every node while ranked ahead of all same-wave
     admitters consumes its run in that same wave (its pods replay first,
     against exactly the state that rejected them) — so the loop always
     terminates and an infeasible head class never costs a dedicated wave.

Pins (`PodArrays.pin`: the one node a pod's required node affinity names on
every term — a DaemonSet's pods, state/encode.py pin_name). A class's pods all
carry one or none does, and those that do are NOT interchangeable: pod k goes
to node k or nowhere. Such a class's feasible nodes in a round are its mask
AND "a pod of its head priority run still waits for this node" (one
scatter-min of the waiting pods' queue positions into [SC, N]); it claims one
pod a node through steps 3-5 like any class (no score window: every pod has
one candidate), and the map back hands each kept node to THE pod that waits
for it, first in queue order where two of the class name one node. All of a
DaemonSet's pods whose nodes pass are admitted in ONE round. Which pinned
pods are consumed is said pod by pod (`_WaveCarry.done`); a pod whose node
refuses it fails by the rules of step 6 read of ITS node: at once where the
class is monotone, with its run on a zero-progress or failing-prefix round
otherwise. All of it sits under `lax.cond`s on "the batch has a pin": pins
are DATA of the one program, and a batch without them pays a few selects.

Fill (step 3 under a packing score). "One pod a node" is the reference's
loop only where a pod's own placement makes its node LESS attractive to the
next of its class (LeastAllocated, BalancedAllocation, the spreading
scores). Under MostAllocated or a rising RequestedToCapacityRatio shape the
loop does the opposite: the node it chose stays the best until it is full.
A class FILLS where the configuration has no falling resource score and the
class reads nothing its own placements move except `used`: no pin, port,
volume, pod (anti-)affinity or spread term, and no preferred term or
selector-spread owner at a positive weight (`_filling`). Such a class
claims, along its score order, as many pods as FIT on the best node, then
on the next (a fit count per node, `min over r of free_r / req_r`, and a
prefix sum against the class's waiting pods: dense [SC, N] work, no scan).
Step 5 takes a claim of k pods as k times the request, kept whole or lost
whole, and the map back hands a kept node to k pods. Whether a class fills
decides WHICH valid execution a round picks, never whether it is one: the
replay invariant below rests on step 5 alone. All of it sits under
`lax.cond`s on "a class of this batch fills": a batch without one runs the
one-a-node claim as before and pays a few selects.

How a round sees its nodes in order (steps 3 and the map back to pods): an
order is a `lax.sort`, and whatever must be seen in that order is an OPERAND
of the sort that makes it — the class's score order (`_score_order`: two keys,
score then the rotated tie-break), a constraint's nodes grouped by topology
domain (`_within_quota`: domain first, then the score order's two keys; the
rank inside a domain is a segmented scan, `_rank_in_run`), an answer put back
in node order (`_to_nodes`: a sort keyed by the permutation), a class's kept
nodes moved to the head of their row. No [SC, N] array is indexed element by
element through a permutation the round has just computed: on the TPU such a
gather or scatter costs ~10 ns an element, more than the sort that made its
indices (PERF.md section 6, PR 38; tests/test_waves.py counts them).

Soundness invariant (tested in tests/test_waves.py): replaying the final
assignment wave-by-wave, each pod in queue order, every placement passes the
full Filter mask at replay time — i.e. the output is a valid greedy execution
of the reference's per-pod loop. Deviations (which valid execution gets
picked) are documented in docs/PARITY.md.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..state.arrays import Array, ClusterTables, PodArrays
from .assign import (AssignResult, AssignState, pod_mask_row, score_row,
                     state_affinity_table, state_spread_counts)
from .fit import _fit
from .interpod import (class_term_membership, domain_of_term,
                       in_domain_counts)
from .lattice import CycleArrays

# plain Python ints only: a module-level jnp scalar would be captured as a
# closure *device array* and hoisted into executable parameters, which
# miscompiles under multi-trace dispatch (jax 0.9 CPU)
_I32_MAX = int(jnp.iinfo(jnp.int32).max)
_I32_MIN = int(jnp.iinfo(jnp.int32).min)

# class-axis tile size for the per-wave dense evaluation (long-context
# tiling; KTPU_CLASS_BLOCK overrides — the bench shapes stay un-tiled)
import os as _os

_CLASS_BLOCK = int(_os.environ.get("KTPU_CLASS_BLOCK", "1024"))
# block size for the per-node contention scan (bounds the [block, N, R]
# temporaries — see the block comment at the scan)
_CONTENTION_BLOCK = int(_os.environ.get("KTPU_CONTENTION_BLOCK", "256"))


class _WaveCarry(NamedTuple):
    state: AssignState
    cursor: Array     # [SC] pods consumed per class (placed or tier-failed)
    placed: Array     # [SC] pods actually placed per class
    node_out: Array   # [P] chosen node per pod (-1 = none)
    wave_out: Array   # [P] wave index each pod was admitted in (-1 = never)
    waves: Array      # scalar i32
    done: Array       # [P] a PINNED pod is consumed (placed or failed): such
                      # pods leave their class's queue out of order, so
                      # `cursor` only counts them
    filled: Array     # [2] i32: pods a FILLING class placed, and the rounds
                      # in which one placed any


def interaction_graph(tables: ClusterTables, cyc: CycleArrays) -> Array:
    """G [SC, SC]: classes whose same-wave admissions could interact through
    affinity/anti-affinity/hard-spread terms (resource/port contention is
    resolved per node instead and needs no edge). Symmetric, no self-edges —
    a class's interaction with itself is handled exactly by the per-domain
    quotas."""
    classes = tables.classes
    S = cyc.TM.shape[0]
    M = cyc.TM.astype(jnp.int32)  # [S, SC] term matches class

    def edges(member: Array) -> Array:  # member: [SC, S]
        return (member.astype(jnp.int32) @ M) > 0  # [SC, SC]

    anti = edges(cyc.has_anti)
    hard_spread_ids = jnp.where(classes.tsc_hard, classes.tsc_term, -1)
    spread = edges(class_term_membership(hard_spread_ids, S))
    aff = edges(class_term_membership(classes.aff_terms, S))
    G = anti | anti.T | spread | spread.T | aff | aff.T
    G = G & classes.valid[:, None] & classes.valid[None, :]
    return G & ~jnp.eye(G.shape[0], dtype=bool)


# the device program's stages carry `jax.named_scope` names, so a profiler
# trace groups its fusions by stage (no run-time cost: metadata only)
@jax.named_scope("class_mask_score")
def _class_mask_score(tables, cyc, state, table, spread):
    """[SC, N] Filter mask + Score for every class against `state` — the
    dense analog of findNodesThatFit + prioritizeNodes, once per class.
    `table` is the round's `state_affinity_table` and `spread` its
    `state_spread_counts`: built once, outside the class axis (and outside
    the class blocks below), every class selecting its own rows.

    Long-context tiling (SURVEY §5 "blockwise tiles over the pod axis"):
    vmapping the full row over SC materializes per-class intermediates like
    [SC, S, N] domain gathers — fine at the class-interned SC of replicated
    workloads, but with thousands of DISTINCT pod specs SC approaches P and
    those temporaries outgrow HBM long before the [SC, N] outputs do. Above
    _CLASS_BLOCK classes the vmap runs under lax.map over class blocks, so
    peak intermediate memory is bounded by block size while outputs stay the
    full lattice (the same shape the rest of the wave consumes)."""
    classes = tables.classes
    SC = classes.valid.shape[0]

    def row(c):
        mask = pod_mask_row(tables, cyc, state, c, jnp.int32(-1),
                            classes.valid[c], table, spread)
        score = score_row(tables, cyc, state, c, table, spread)
        return mask, jnp.where(mask, score, -jnp.inf)

    if SC <= _CLASS_BLOCK:
        return jax.vmap(row)(jnp.arange(SC))
    n_blocks = -(-SC // _CLASS_BLOCK)
    blocks = jnp.arange(n_blocks * _CLASS_BLOCK, dtype=jnp.int32).reshape(
        n_blocks, _CLASS_BLOCK)
    # padded tail indexes clamp to SC-1; the duplicate rows are sliced off
    masks, scores = lax.map(
        lambda blk: jax.vmap(row)(jnp.minimum(blk, SC - 1)), blocks)
    return (masks.reshape(-1, masks.shape[-1])[:SC],
            scores.reshape(-1, scores.shape[-1])[:SC])


def _score_order(neg_score: Array, rot_pos: Array, offs: Array, *riders):
    """Every class's nodes best first → (order_n [SC, N], *riders in that
    order). ONE two-key sort in node space: score descending (`neg_score` =
    -score; a masked node's is +inf, so it sorts last), ties by `rot_pos`,
    the node's position in the class's rotated row (offs: [SC] rotation).
    What must be seen in the order rides the sort as an operand: an element
    gather through a permutation costs more here than the sort that made
    it."""
    N = neg_score.shape[1]
    _, pos, *out = lax.sort((neg_score, rot_pos) + riders, dimension=1,
                            num_keys=2)
    return ((pos + offs[:, None]) % N, *out)


def _rank_in_run(key: Array) -> Array:
    """key [N], sorted → every element's 0-based rank inside its run of
    equal keys: its index less the index its run starts at, and that start
    is a running max over the run boundaries' indices (position 0 is one).
    A segmented scan: no table over the key's range, which for a
    hostname-keyed constraint is N itself."""
    idx = jnp.arange(key.shape[0], dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), bool), key[1:] != key[:-1]])
    return idx - lax.cummax(jnp.where(starts, idx, 0), axis=0)


def _to_nodes(node: Array, bits: Array) -> Array:
    """bits [..., N], each said of the node beside it in `node` (a
    permutation of the node axis per row) → the same bits in node order. A
    scatter through a permutation is a sort keyed by the permutation with
    the values riding."""
    return lax.sort((node, bits), dimension=node.ndim - 1, num_keys=1)[1]


def _within_quota(neg_score: Array, rot_pos: Array, off: Array, dom: Array,
                  D: int, quota_n: Array | int) -> Array:
    """One class, one constraint slot → [N] bool in node order: the node is
    among the first `quota_n` of its topology domain in the class's score
    order. dom [N]: the node's domain (-1 = none: those share bucket D);
    quota_n: the domain's cap said of each node [N], or one cap for all.

    ONE sort groups the nodes by domain, best first inside each group (the
    score order's own two keys break the domain's ties), the cap riding as an
    operand: O(N log N), never an [N, D] one-hot (D can be N itself), and
    rank-in-domain is the index in the grouped order less the group's
    start."""
    N = dom.shape[0]
    riders = () if isinstance(quota_n, int) else (quota_n,)
    dom_g, _, pos_g, *cap_g = lax.sort(
        (jnp.where(dom >= 0, dom, D), neg_score, rot_pos) + riders,
        num_keys=3)
    ok_g = _rank_in_run(dom_g) < (cap_g[0] if cap_g else quota_n)
    return _to_nodes((pos_g + off) % N, ok_g)


@jax.named_scope("domain_quota_pass")
def _domain_quota_pass(tables, cyc, spread, allowed, neg_score, rot_pos,
                       offs):
    """AND per-domain admission quotas into `allowed` [SC, N] (node order).
    Quotas keep same-wave same-class admissions from violating hard spread /
    self-anti-affinity when replayed sequentially. `spread` is the round's
    `state_spread_counts`; `neg_score`, `rot_pos` [SC, N] are the two keys
    of the class's score order (assign_waves.body), `offs` [SC] the rotation
    that turns a `rot_pos` back into its node."""
    classes = tables.classes
    nodes = tables.nodes
    terms = tables.terms
    D = cyc.D
    SC, N = allowed.shape
    TS = classes.tsc_term.shape[1]
    AN = classes.anti_terms.shape[1]

    def key_domain(topo_key):
        """[N] every node's domain under the key, -1 where it has none."""
        return domain_of_term(nodes, topo_key[None])[0][0]

    def slot_quota(c, dom, active, quota_n):
        return ~active | _within_quota(neg_score[c], rot_pos[c], offs[c],
                                       dom, D, quota_n)

    # --- hard topology-spread slots (only self-matching classes move their
    # own counts; others are quota-free here and guarded by the graph).
    # Slots are a vmapped axis, not a Python loop: the traced graph stays the
    # same size no matter how many TS/AN slots the constraint schema needs.
    # Each family is under lax.cond: a batch with no active slots anywhere
    # (e.g. gang jobs with plain resource requests) skips the [SC·slots]
    # sorts entirely at runtime. ---
    def spread_slot(c, t):
        s_id = classes.tsc_term[c, t]
        s = jnp.maximum(s_id, 0)
        active = (
            (s_id >= 0) & classes.tsc_hard[c, t] & cyc.TM[s, c]
        )
        active = active & spread.any_eligible[c, t]
        # the domain's cap, said of each of its nodes: the count is the
        # domain's on every one of them. (A node without the key reads
        # count 0 and shares bucket D; the slot's Filter row refuses it.)
        cap = jnp.clip(
            classes.tsc_maxskew[c, t] + spread.min_cnt[c, t]
            - spread.cnt[c, t], 0, _I32_MAX)
        return slot_quota(c, key_domain(terms.topo_key[s]), active, cap)

    def apply_spread(allowed):
        rows = jax.vmap(
            lambda c: jax.vmap(lambda t: spread_slot(c, t))(
                jnp.arange(TS, dtype=jnp.int32))
        )(jnp.arange(SC, dtype=jnp.int32))        # [SC, TS, N]
        return allowed & rows.all(axis=1)

    any_spread = ((classes.tsc_term >= 0) & classes.tsc_hard
                  & classes.valid[:, None]).any()
    allowed = lax.cond(any_spread, apply_spread, lambda a: a, allowed)

    # --- self-matching anti-affinity slots: one per domain per wave ---
    def anti_slot(c, t):
        s_id = classes.anti_terms[c, t]
        s = jnp.maximum(s_id, 0)
        k = terms.topo_key[s]
        active = (s_id >= 0) & cyc.TM[s, c] & (k >= 0)
        return slot_quota(c, key_domain(k), active, 1)

    def apply_anti(allowed):
        rows = jax.vmap(
            lambda c: jax.vmap(lambda t: anti_slot(c, t))(
                jnp.arange(AN, dtype=jnp.int32))
        )(jnp.arange(SC, dtype=jnp.int32))        # [SC, AN, N]
        return allowed & rows.all(axis=1)

    any_anti = ((classes.anti_terms >= 0)
                & classes.valid[:, None]).any()
    return lax.cond(any_anti, apply_anti, lambda a: a, allowed)


def _escape_cap(tables, cyc, state, r, table):
    """Required-affinity first-pod escape: a class whose required terms have
    zero potential matches (predicates.go:1436-1440) admits at most ONE pod
    this wave, so the followers see its counts next wave. The totals are
    affinity_rows' own (`table`: the round's `state_affinity_table`)."""
    classes = tables.classes
    D = cyc.D

    def one(c):
        ats = classes.aff_terms[c]
        active = ats >= 0
        tot = in_domain_counts(ats, tables.terms, state.CNT, tables.nodes,
                               D, table, cyc.SAME)[1]
        return active.any() & (jnp.sum(jnp.where(active, tot, 0)) == 0)

    escape = jax.vmap(one)(jnp.arange(classes.valid.shape[0]))
    return jnp.where(escape, jnp.minimum(r, 1), r)


def _pin_nodes(nodes, pin: Array) -> Array:
    """pin [P] node-name ids → [P] the slot of the valid node that bears the
    name, -1 where the pod has no pin or no node bears it. One fused
    compare-and-reduce over [P, N], once a dispatch."""
    N = nodes.valid.shape[0]
    hit = ((nodes.name_id[None, :] == pin[:, None]) & nodes.valid[None, :]
           & (pin[:, None] >= 0))
    return jnp.max(jnp.where(hit, jnp.arange(N, dtype=jnp.int32)[None, :],
                             -1), axis=1)


def _filling(tables: ClusterTables, cyc: CycleArrays) -> Array:
    """[SC] bool: the classes that fill (module docstring): the score of the
    node a pod of the class lands on does not fall for the next one."""
    classes, w = tables.classes, cyc.ecfg
    rising = (w.rtc_y[1:] >= w.rtc_y[:-1]).all()
    no_falling_score = ((w.w_least == 0) & (w.w_balanced == 0)
                        & ((w.w_rtc == 0) | rising))
    none = lambda ids: ~(ids >= 0).any(axis=1)

    def empty(ids, *words):
        """The class names no set, or (the encoder's set 0) an empty one."""
        at = jnp.maximum(ids, 0)
        return (ids < 0) | jnp.stack(
            [(w[at] == 0).all(axis=1) for w in words]).all(axis=0)

    ps, vs = tables.portsets, tables.volsets
    plain = (none(classes.aff_terms) & none(classes.anti_terms)
             & none(classes.tsc_term)
             & empty(classes.portset, ps.pair_words, ps.wild_words,
                     ps.trip_words)
             & empty(classes.volset, vs.any_words)
             & ~(classes.vol_priv > 0).any(axis=1))
    unmoved = (((w.w_interpod == 0) | (none(classes.paff_terms)
                                       & none(classes.panti_terms)))
               & ((w.w_ssel == 0) | none(classes.ssel_terms)))
    return classes.valid & no_falling_score & plain & unmoved


def _fit_counts(req: Array, free: Array) -> Array:
    """req [SC, R], free [N, R] -> [SC, N] how many pods of the class fit
    on the node beside what it holds: the least `free_r / req_r` over the
    resources the class asks for (the pod slot is one of them), at least 1.
    Read only where the Filter row passed, so where one fits. A resource at
    a time, as ops/fit.py rtc_score_row."""
    least = jnp.full((req.shape[0], free.shape[0]), _I32_MAX, jnp.int32)
    for r in range(req.shape[1]):
        ask = req[:, r, None]                                 # [SC, 1]
        least = jnp.minimum(least, jnp.where(
            ask > 0, lax.div(free[None, :, r], jnp.maximum(ask, 1)),
            _I32_MAX))
    return jnp.maximum(least, 1)


def assign_waves(
    tables: ClusterTables,
    cyc: CycleArrays,
    pods: PodArrays,
    init: AssignState,
    max_waves: int | None = None,
    return_waves: bool = False,
) -> AssignResult:
    """Drop-in replacement for ops/assign.py:assign_batch (same signature,
    same result type). See the module docstring for the algorithm."""
    classes = tables.classes
    nodes = tables.nodes
    SC = classes.valid.shape[0]
    N = nodes.valid.shape[0]
    P = pods.valid.shape[0]
    R = tables.reqs.vec.shape[1]

    G = interaction_graph(tables, cyc)
    req_by_class = tables.reqs.vec[jnp.maximum(classes.rid, 0)]  # [SC, R]

    # classes whose Filter feasibility is MONOTONE within a dispatch: state
    # only tightens for them (used/CNT/ports/volumes grow; anti-affinity
    # only blocks more). Required pod-affinity (new matches open nodes) and
    # hard spread (a rising domain-min lifts other domains' quotas) are the
    # only relaxing predicates; classes without either, once infeasible on
    # every node, stay infeasible for the rest of the dispatch.
    mono = (
        ~(classes.aff_terms >= 0).any(axis=1)
        & ~((classes.tsc_term >= 0) & classes.tsc_hard).any(axis=1)
    )

    # --- queue order, grouped by class (activeQ comparator within class) ---
    cls_safe = jnp.where(pods.valid, pods.cls, SC)
    sorted_pods = jnp.lexsort((pods.creation, -pods.priority, cls_safe))  # [P]
    class_total = (
        jnp.zeros((SC + 1,), jnp.int32)
        .at[cls_safe].add(1)[:SC]
    )
    class_offset = jnp.cumsum(class_total) - class_total  # [SC] exclusive
    sorted_pods_pad = jnp.concatenate(
        [sorted_pods, jnp.full((1,), P, jnp.int32)])
    cls_of_pod = jnp.minimum(cls_safe, SC - 1)
    # every pod's place in its class's queue order: the inverse of the sort,
    # taken once a dispatch
    pos_of_pod = jnp.zeros((P,), jnp.int32).at[sorted_pods].set(
        jnp.arange(P, dtype=jnp.int32)) - class_offset[cls_of_pod]
    node_ids = jnp.arange(N, dtype=jnp.int32)

    # ---- pins: see the module docstring. `any_pin` guards every pinned
    # step below, so a batch without pins runs none of them ----
    has_pin = pods.valid & (pods.pin >= 0)                     # [P]
    any_pin = has_pin.any()
    cls_pinned = jnp.zeros((SC,), bool).at[cls_of_pod].max(has_pin)
    pin_node = lax.cond(any_pin, lambda: _pin_nodes(nodes, pods.pin),
                        lambda: jnp.full((P,), -1, jnp.int32))
    on_pin = has_pin & (pin_node >= 0)
    pin_safe = jnp.maximum(pin_node, 0)
    no_pods = jnp.zeros((P,), bool)

    # ---- fill: see the module docstring. `any_fill` guards every filling
    # step below ----
    fills = (_filling(tables, cyc) & ~cls_pinned & (class_total > 0))  # [SC]
    any_fill = fills.any()

    def body(carry: _WaveCarry) -> _WaveCarry:
        state, cursor, placed, node_out, wave_out, waves, done, filled = carry
        remaining = class_total - cursor
        active = classes.valid & (remaining > 0)

        # where a class's queue stands: the cursor, or for a pinned class
        # the first of its pods still waiting
        def pinned_heads():
            first = jnp.full((SC,), P, jnp.int32).at[cls_of_pod].min(
                jnp.where(has_pin & ~done, pos_of_pod, P))
            return jnp.where(cls_pinned, first, cursor)

        head = lax.cond(any_pin, pinned_heads, lambda: cursor)

        # next pending pod per class. Admission is CROSS-TIER: a class needs
        # no global priority-tier gate because everything priority order can
        # observe is already serialized in rank order — interacting classes
        # through the graph block below, same-node resources/ports/volumes
        # through the rank-ordered cumulative passes. A lower-priority pod
        # admitted alongside a higher-priority one replays after it
        # (wave, priority, creation) and sees identical committed state.
        nxt = sorted_pods_pad[jnp.minimum(class_offset + head, P)]
        nxt_ok = active & (nxt < P)
        nxt_safe = jnp.minimum(nxt, P - 1)
        # i32 min is the neutral element, not a magic sentinel: run counts
        # also require nxt_ok, so real INT32_MIN priorities still work
        nxt_pri = jnp.where(nxt_ok, pods.priority[nxt_safe], _I32_MIN)
        nxt_cre = jnp.where(nxt_ok, pods.creation[nxt_safe], _I32_MAX)

        # length of each class's CURRENT priority run (pods at the class's
        # own head priority, at/after the cursor) — the unit that fails
        # together when the head pod is infeasible against frozen state
        run_pod = (
            pods.valid & (pods.priority == nxt_pri[cls_of_pod])
            & jnp.where(has_pin, ~done, pos_of_pod >= cursor[cls_of_pod])
        )
        run_cnt = (
            jnp.zeros((SC,), jnp.int32).at[cls_of_pod].add(
                run_pod.astype(jnp.int32))
        )
        r = jnp.where(nxt_ok, jnp.minimum(remaining, run_cnt), 0)

        table = state_affinity_table(tables, cyc, state, SC)
        spread = state_spread_counts(tables, cyc, state, SC)
        mask, score = _class_mask_score(tables, cyc, state, table, spread)
        cls_mask = mask & nxt_ok[:, None]

        # a pinned class's nodes: those a pod of its run still waits for,
        # and for each the first such pod's queue position (P: none)
        def pinned_waiting():
            at = jnp.where(run_pod & on_pin, pin_node, N)
            return jnp.full((SC, N), P, jnp.int32).at[cls_of_pod, at].min(
                pos_of_pod, mode="drop")

        waits = lax.cond(any_pin, pinned_waiting,
                         lambda: jnp.full((SC, N), P, jnp.int32))
        mask = cls_mask & (~cls_pinned[:, None] | (waits < P))
        # score-window admission (EngineConfig.w_window): a class only
        # admits on nodes within the window of its per-class feasible max
        # this wave, so decisive score gaps (preferAvoidPods, strong
        # preferences) aren't steamrolled by same-wave intra-class
        # spreading. The max itself always qualifies → feasibility (and
        # the early-fail rule's mask.any) is unchanged; ties are
        # unaffected. Nodes outside the window become admissible in later
        # waves once the leading tier fills and the class max drops.
        best = jnp.max(jnp.where(mask, score, -jnp.inf), axis=1,
                       keepdims=True)
        adm_mask = mask & ((score >= best - cyc.ecfg.w_window)
                           | cls_pinned[:, None])
        r = _escape_cap(tables, cyc, state, r, table)

        # independent set over the interaction graph, queue-rank order:
        # a class yields to any earlier-ranked ACTIVE class it interacts
        # with (in-tier or not — the earlier class admits first, this wave
        # or a later one). Inactive classes rank LAST via the explicit
        # primary key (negating their _I32_MIN sentinel priority overflows
        # i32 and would rank them first, handing active classes nonzero
        # ranks — and nonzero tie-rotation offsets — they must not have);
        # priority-descending uses the order-preserving unsigned bias, so
        # real INT32_MIN priorities sort correctly without x64.
        pri_desc = ~(nxt_pri.astype(jnp.uint32) ^ jnp.uint32(0x80000000))
        rank_key = jnp.lexsort((nxt_cre, pri_desc, ~nxt_ok))  # [SC] perm
        crank = jnp.zeros((SC,), jnp.int32).at[rank_key].set(
            jnp.arange(SC, dtype=jnp.int32))
        earlier = crank[None, :] < crank[:, None]            # [SC, SC]
        blocked = (G & earlier & nxt_ok[None, :]).any(axis=1)
        attempted = nxt_ok & ~blocked & (r > 0)
        r = jnp.where(attempted, r, 0)

        # per-class admission: top-r feasible nodes by score, domain quotas.
        # Equal-score ties resolve from a rotated start index keyed to the
        # class's QUEUE RANK within this batch — the reference's round-robin
        # node offset (generic_scheduler.go:502 nextStartNodeIndex): on a
        # uniform cluster every class's score row is CONSTANT, and without
        # rotation all classes pile onto the same lowest-index nodes, so
        # rank-ordered contention admits a trickle per wave (observed: 69
        # waves at 2k nodes × 1.4k classes; ~7 with rotation). The rank (not
        # the global interned class index) keeps any single-pending-class
        # batch at offset 0 → identical to the sequential scan's
        # argmax-lowest-index (PARITY #1, tests' singleton agreement).
        offs = (crank * 97) % N
        neg_score = -score
        rot_pos = (node_ids[None, :] - offs[:, None]) % N     # [SC, N]
        allowed_n = _domain_quota_pass(
            tables, cyc, spread, adm_mask, neg_score, rot_pos, offs)
        # K [SC, N]: the pods the class claims on the node
        def claim_one():
            order_n, allowed = _score_order(neg_score, rot_pos, offs,
                                            allowed_n)
            grank = jnp.cumsum(allowed.astype(jnp.int32), axis=1) - 1
            return _to_nodes(order_n, allowed & (grank < r[:, None])
                             ).astype(jnp.int32)

        def claim_fill():
            cnt_n = jnp.where(
                fills[:, None],
                _fit_counts(req_by_class, nodes.alloc - state.used), 1)
            order_n, allowed, cnt = _score_order(neg_score, rot_pos, offs,
                                                 allowed_n, cnt_n)
            take = jnp.where(allowed, cnt, 0)
            before = jnp.cumsum(take, axis=1) - take
            return _to_nodes(order_n,
                             jnp.clip(r[:, None] - before, 0, take))

        K = lax.cond(any_fill, claim_fill, claim_one)
        A = K > 0

        # per-node cross-class resolution in queue-rank order, as a scan
        # over CLASS BLOCKS: the cumulative passes need [block, N, …]
        # temporaries only, never [SC, N, R] — at thousands of distinct
        # classes (gang jobs each carry their own labels → their own class)
        # the un-blocked cumsum chain was an HBM-OOM worker crash at
        # 5k nodes × 100k pods. Carries thread the exact same exclusive
        # prefixes across blocks, so the result is bit-identical.
        cord = rank_key                                       # [SC] perm
        K_ord = K[cord]
        req_ord = req_by_class[cord]                          # [SC, R]
        ps_ord = classes.portset[cord]
        psafe = jnp.maximum(ps_ord, 0)
        has_p = (ps_ord >= 0)
        pairw = tables.portsets.pair_words[psafe]             # [SC, PWp]
        wildw = tables.portsets.wild_words[psafe]
        tripw = tables.portsets.trip_words[psafe]
        vs_ord = classes.volset[cord]
        vsafe = jnp.maximum(vs_ord, 0)
        vpriv = classes.vol_priv[cord]                        # [SC, DR]
        has_v = (vs_ord >= 0) | (vpriv > 0).any(axis=1)
        in_set = (vs_ord >= 0)[:, None]
        vanyw = jnp.where(in_set, tables.volsets.any_words[vsafe], 0)  # [SC, VW]
        vrww = jnp.where(in_set, tables.volsets.rw_words[vsafe], 0)

        B = min(_CONTENTION_BLOCK, SC)
        nb = -(-SC // B)
        pad = nb * B - SC

        def blocks_of(x):  # pad with inert rows (no admission, zero words)
            if pad:
                z = jnp.zeros((pad,) + x.shape[1:], x.dtype)
                x = jnp.concatenate([x, z])
            return x.reshape((nb, B) + x.shape[1:])

        shift = lambda M: jnp.concatenate(
            [jnp.zeros_like(M[:1]), M[:-1]], axis=0)
        or_red = lambda k, W: lax.associative_scan(
            jnp.bitwise_or, jnp.where(k, W[:, None, :], 0), axis=0)[-1]

        def block(carry, xs):
            cum_used, c_pa, c_pw, c_pt, c_va, c_vr, c_vc = carry
            K_b, req_b, hp_b, pw_b, ww_b, tw_b, hv_b, va_b, vr_b, vp_b = xs
            # a claim of k pods asks k times the request, whole or not at all
            add = K_b[:, :, None] * req_b[:, None, :]
            cum_exc = (jnp.cumsum(add, axis=0) - add) + cum_used[None]
            # earlier same-wave classes consume free space; the pod itself
            # must fit per PodFitsResources semantics (zero scalar requests
            # ignore that scalar's free — fit._fit, predicates.go:800-845)
            free = nodes.alloc[None] - state.used[None] - cum_exc
            fits = _fit(jnp.maximum(add, req_b[:, None, :]), free)
            keep = (K_b > 0) & fits

            # ports: exclusive prefix over keep-after-resources (a class
            # that itself loses the port check still shadows later ones —
            # conservative, matching the un-blocked pass)
            kp = (keep & hp_b[:, None])[:, :, None]
            scan_or = lambda W: lax.associative_scan(
                jnp.bitwise_or, jnp.where(kp, W[:, None, :], 0), axis=0)
            inc_p, inc_w, inc_t = scan_or(pw_b), scan_or(ww_b), scan_or(tw_b)
            exc_p = shift(inc_p) | c_pa[None]
            exc_w = shift(inc_w) | c_pw[None]
            exc_t = shift(inc_t) | c_pt[None]
            conflict = (
                ((ww_b[:, None, :] & exc_p) != 0)
                | ((pw_b[:, None, :] & exc_w) != 0)
                | ((tw_b[:, None, :] & exc_t) != 0)
            ).any(-1)
            keep2 = keep & (~hp_b[:, None] | ~conflict)

            # volume conflict/limits against same-wave earlier classes on
            # the same node: exclusive-prefix OR of the shared volumes'
            # words and exclusive-prefix SUM of the counts of volumes that
            # are one pod's alone, then conflict + limits
            kv = (keep2 & hv_b[:, None])[:, :, None]
            addc = jnp.where(kv, vp_b[:, None, :], 0)         # [B, N, DR]
            exc_vc = (jnp.cumsum(addc, axis=0) - addc) + c_vc[None]
            scan_orv = lambda W: lax.associative_scan(
                jnp.bitwise_or, jnp.where(kv, W[:, None, :], 0), axis=0)
            exc_va = shift(scan_orv(va_b)) | c_va[None]
            exc_vr = shift(scan_orv(vr_b)) | c_vr[None]
            tot_any = state.vol_any[None] | exc_va            # [B, N, VW]
            tot_rw = state.vol_rw[None] | exc_vr
            vconf = (
                ((va_b[:, None, :] & tot_rw) != 0)
                | ((vr_b[:, None, :] & tot_any) != 0)
            ).any(-1)
            after_v = tot_any | va_b[:, None, :]
            vcnt = jax.lax.population_count(
                after_v[:, :, None, :] & tables.drv_masks[None, None, :, :]
            ).sum(-1).astype(jnp.int32) + (
                state.vol_cnt[None] + exc_vc + vp_b[:, None, :])  # [B, N, DR]
            vlim = nodes.vol_limit[None]                      # [1, N, DR]
            vlim_ok = ((vlim < 0) | (vcnt <= vlim)).all(-1)
            keep3 = keep2 & (~hv_b[:, None] | (~vconf & vlim_ok))

            # carries: resources advance over A_b (pre-filter, as above);
            # port words over keep-after-resources; volume words over
            # keep-after-ports. Committed words (state update) come from
            # the FINAL keep and are emitted per block.
            carry2 = (
                cum_used + add.sum(axis=0),
                c_pa | inc_p[-1], c_pw | inc_w[-1], c_pt | inc_t[-1],
                c_va | scan_orv(va_b)[-1], c_vr | scan_orv(vr_b)[-1],
                c_vc + addc.sum(axis=0),
            )
            kp2 = (keep3 & hp_b[:, None])[:, :, None]
            kv2 = (keep3 & hv_b[:, None])[:, :, None]
            committed = (
                or_red(kp2, pw_b), or_red(kp2, ww_b), or_red(kp2, tw_b),
                or_red(kv2, va_b), or_red(kv2, vr_b),
                jnp.where(kv2, vp_b[:, None, :], 0).sum(axis=0),
            )
            return carry2, (keep3, committed)

        Wp = pairw.shape[1]
        VW = vanyw.shape[1]
        carry0 = (
            jnp.zeros((N, R), jnp.int32),
            jnp.zeros((N, Wp), pairw.dtype),
            jnp.zeros((N, Wp), wildw.dtype),
            jnp.zeros((N, Wp), tripw.dtype),
            jnp.zeros((N, VW), vanyw.dtype),
            jnp.zeros((N, VW), vrww.dtype),
            jnp.zeros((N, vpriv.shape[1]), jnp.int32),
        )
        _, (keep_b, committed_b) = lax.scan(
            block, carry0,
            (blocks_of(K_ord), blocks_of(req_ord), blocks_of(has_p),
             blocks_of(pairw), blocks_of(wildw), blocks_of(tripw),
             blocks_of(has_v), blocks_of(vanyw), blocks_of(vrww),
             blocks_of(vpriv)))
        keep = keep_b.reshape(nb * B, N)[:SC]
        or_blocks = lambda x: lax.associative_scan(
            jnp.bitwise_or, x, axis=0)[-1]
        orp, orw, ort, orva, orvr = (or_blocks(cb) for cb in committed_b[:5])
        addvc = committed_b[5].sum(axis=0)                    # [N, DR]

        A_final = jnp.zeros_like(A).at[cord].set(keep)
        K_final = jnp.where(A_final, K, 0)
        m = K_final.sum(axis=1)                               # [SC]
        total = m.sum()

        # ---- commit ----
        with jax.named_scope("wave_commit"):
            Ai = K_final
            used2 = state.used + jnp.einsum("cn,cr->nr", Ai, req_by_class)
            CNT2 = state.CNT + cyc.TM.astype(jnp.int32) @ Ai
            HOLD2 = state.HOLD + cyc.has_anti.T.astype(jnp.int32) @ Ai
            # HIGHEST: at the TPU's default precision an f32 matmul rounds its
            # operands to bf16, and a weight sum such as 301 is not a bf16 — the
            # scan engine's exact adds of the same WCOLS entries would diverge
            WSYM2 = state.WSYM + jnp.matmul(cyc.WCOLS, Ai.astype(jnp.float32),
                                            precision=lax.Precision.HIGHEST)
            state2 = AssignState(
                used=used2,
                ppa=state.ppa | orp, ppw=state.ppw | orw, ppt=state.ppt | ort,
                CNT=CNT2, HOLD=HOLD2, WSYM=WSYM2,
                vol_any=state.vol_any | orva, vol_rw=state.vol_rw | orvr,
                vol_cnt=state.vol_cnt + addvc,
            )

        # ---- map admissions back to pods: a class's kept nodes, best first
        # (score, then node index), go to its next m pods in queue order.
        # The kept ride to the head of their row in one sort, and every POD
        # looks its node up: P lookups, where a write per (class, node)
        # would be SC·N updates of which sum(m) ≤ P carry anything ----
        j = pos_of_pod - cursor[cls_of_pod]
        won = pods.valid & ~has_pin & (j >= 0) & (j < m[cls_of_pod])
        kept_keys = (~A_final, neg_score,
                     jnp.broadcast_to(node_ids, (SC, N)))

        def map_one():
            _, _, kept_nodes = lax.sort(kept_keys, dimension=1, num_keys=2,
                                        is_stable=True)
            return kept_nodes[cls_of_pod, jnp.clip(j, 0, N - 1)]

        def map_fill():
            # a kept node goes to as many pods as it was kept for: the
            # classes' rows end to end, each kept node's running count of
            # pods, are ONE ascending sequence, and a pod's place in it is
            # a binary search (P of them, each log(SC N) lookups)
            _, _, kept_nodes, kept_k = lax.sort(
                kept_keys + (K_final,), dimension=1, num_keys=2,
                is_stable=True)
            base = jnp.cumsum(m) - m                          # [SC]
            upto = (base[:, None] + jnp.cumsum(kept_k, axis=1)).reshape(-1)
            at = jnp.searchsorted(upto, base[cls_of_pod] + j, side="right")
            return kept_nodes.reshape(-1)[jnp.clip(at, 0, SC * N - 1)]

        node_out2 = jnp.where(won, lax.cond(any_fill, map_fill, map_one),
                              node_out)

        # Failure consumption, two rules (both replay-sound):
        #  * global zero progress ⇒ state is frozen ⇒ every attempting
        #    class's priority run fails exactly as pod-by-pod in the scan;
        #  * EARLY per-class fail: an attempted class whose Filter mask is
        #    false on every node, ranked ahead of every class that admitted
        #    this wave, consumes its run NOW — its pods replay before any
        #    of this wave's placements, against exactly the wave-start
        #    state that rejected them. (Filter-infeasible only: a class
        #    losing to same-wave quota/contention retries next wave, where
        #    the sequential outcome may differ.)
        fail = total == 0
        infeasible = attempted & ~mask.any(axis=1)
        # monotone classes consume EVERYTHING once nowhere-feasible (state
        # never relaxes for them this dispatch). Non-monotone classes (a
        # later placement could open nodes for them: required affinity,
        # hard spread) consume only when they sit in the FAILING PREFIX of
        # the rank order — every class ranked before them this wave is
        # itself infeasible-attempted or inactive, so their sequential
        # replay position pops against exactly the wave-start state that
        # rejected them. (Ranked-behind a blocked or admitting class, they
        # retry: that class's later placements may feed their predicates.)
        ord_fail = (infeasible | ~nxt_ok)[rank_key]
        prefix = jnp.cumprod(ord_fail.astype(jnp.int32)) > 0
        in_prefix = jnp.zeros((SC,), bool).at[rank_key].set(prefix)
        early_fail = infeasible & (mono | in_prefix)
        run_left = jnp.minimum(run_cnt, remaining)
        consume = jnp.where(infeasible & mono, remaining,
                            jnp.where((fail & attempted) | early_fail,
                                      run_left, m))

        # pinned pods, one by one: a kept node goes to the pod that waits
        # for it; a pod fails by the two rules above read of ITS node (a
        # monotone class's pod as soon as its node refuses it, whatever its
        # priority: the state only tightens), or with its run
        def pinned_outcome():
            c = cls_of_pod
            mine = run_pod & on_pin & (waits[c, pin_safe] == pos_of_pod)
            won_p = mine & A_final[c, pin_safe]
            refused = ~(on_pin & cls_mask[c, pin_safe])
            lost_p = (has_pin & ~done & ~won_p & attempted[c]
                      & ((mono[c] & refused)
                         | (run_pod & (fail | early_fail[c]))))
            gone = jnp.zeros((SC,), jnp.int32).at[c].add(
                (won_p | lost_p).astype(jnp.int32))
            return won_p, lost_p, jnp.where(cls_pinned, gone, consume)

        won_p, lost_p, consume = lax.cond(
            any_pin, pinned_outcome, lambda: (no_pods, no_pods, consume))
        node_out2 = jnp.where(won_p, pin_node, node_out2)
        wave_out2 = jnp.where(won | won_p, waves, wave_out)
        fill_m = jnp.sum(jnp.where(fills, m, 0))
        return _WaveCarry(
            state=state2, cursor=cursor + consume, placed=placed + m,
            node_out=node_out2, wave_out=wave_out2, waves=waves + 1,
            done=done | won_p | lost_p,
            filled=filled + jnp.stack([fill_m, (fill_m > 0).astype(
                jnp.int32)]),
        )

    cap = jnp.int32(max_waves if max_waves is not None else 2 * P + 2)

    def cond(carry: _WaveCarry) -> Array:
        remaining = (class_total - carry.cursor)
        return ((remaining > 0) & tables.classes.valid).any() & (
            carry.waves < cap)

    init_carry = _WaveCarry(
        state=init,
        cursor=jnp.zeros((SC,), jnp.int32),
        placed=jnp.zeros((SC,), jnp.int32),
        node_out=jnp.full((P,), -1, jnp.int32),
        wave_out=jnp.full((P,), -1, jnp.int32),
        waves=jnp.int32(0),
        done=no_pods,
        filled=jnp.zeros((2,), jnp.int32),
    )
    final = lax.while_loop(cond, body, init_carry)
    node = final.node_out
    result = AssignResult(
        node=node, feasible=node >= 0, state=final.state, rounds=final.waves,
        fill=jnp.concatenate([fills.sum(dtype=jnp.int32)[None],
                              final.filled]))
    if return_waves:
        return result, final.wave_out
    return result
