"""Gang/co-scheduling: all-or-nothing pod groups on the wave engine
(BASELINE config 5 — 5k nodes × 100k pods in groups).

The reference has no in-tree gang scheduler (BASELINE.md: out-of-tree
coscheduling only); the semantics implemented here are the sig-scheduling
coscheduling protocol — a group of pods carrying a PodGroup with
`spec.minMember` either gets ≥ minMember members placed (counting members
already bound) or none at all — expressed the TPU way:

  1. run the wave engine (ops/waves.py) over the full batch: every group's
     members participate in the dense admission exactly like ungrouped pods,
     so a feasible gang places in the SAME single dispatch as everything
     else — no per-group what-if round-trips;
  2. count per-group placements with one scatter-add; groups that reached
     `needed` commit as-is;
  3. underfilled groups are rejected and the wave fixpoint RESTARTS from the
     original cycle state with the rejected groups' pods masked out — the
     device-resident analog of the Permit plugin rejecting every waiting
     member of a timed-out group (framework/v1alpha1/interface.go:339 +
     waiting_pods_map.go: un-reserving a group returns its resources before
     anyone else binds). Restarting (instead of subtracting the partial
     group post-hoc) is what keeps the committed assignment a valid greedy
     execution: pods that placed *because of* a rejected member (required
     affinity) are re-decided, never left dangling.
  4. rejection order resolves inter-group contention: when two groups split
     a resource pocket and both underfill, the LOWEST-ranked group (min
     member priority, then youngest) is rejected first and the survivors
     re-place into the freed capacity — the batched analog of the
     coscheduling plugin's per-group Permit timeout racing, made
     deterministic. After `soft_rounds` single-rejections the remaining
     underfilled groups reject together (bulk tail for many-group storms).

The loop is a lax.while_loop around the wave fixpoint: zero host round-trips,
one compiled program. Each iteration rejects ≥1 group, so it terminates in
≤ GR+1 iterations; with no underfilled groups it runs the waves exactly once
(the common case pays nothing over plain assign_waves).

Soundness invariant (tests/test_gang.py): for every group, either
placed ≥ needed or placed == 0 — no partial group ever commits — and the
final assignment replays through the sequential oracle like any wave result.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from ..state.arrays import Array, ClusterTables, PodArrays
from .assign import AssignResult, AssignState
from .lattice import CycleArrays
from .waves import assign_waves


class GangArrays(NamedTuple):
    """Per-cycle gang inputs (built host-side: state/encode.py
    build_gang_arrays)."""

    group: Array   # [P] i32 — group id per pending pod, -1 ungrouped
    needed: Array  # [GR] i32 — members still required (minMember - bound)
    valid: Array   # [GR] bool — group has members in this batch
    rank: Array    # [GR] i32 — rejection priority; argmax rejects first


class GangVerdict(NamedTuple):
    """What the loop decided, per group, for the host's record, counters
    and FailedScheduling Events (sched/scheduler.py)."""

    rejected: Array    # [GR] bool — refused this dispatch: none placed
    placed: Array      # [GR] i32 — members that fit in the run that
    #                    refused the group (0 where it was not refused)
    rounds: Array      # scalar i32 — wave fixpoints this dispatch ran
    groups: Array      # scalar i32 — groups with a member in the batch


class _GangCarry(NamedTuple):
    rejected: Array    # [GR] bool
    short: Array       # [GR] i32 — `placed` of the run that rejected it
    under: Array       # [GR] bool — underfilled in the latest run
    placed: Array      # [GR] i32 — members placed in the latest run
    rounds: Array      # scalar i32
    node: Array        # [P] i32 latest assignment
    feasible: Array    # [P] bool
    waves: Array       # [P] i32 wave index per pod (tests/replay)
    state: AssignState


def _placed_per_group(gang: GangArrays, pods: PodArrays,
                      feasible: Array) -> Array:
    GR = gang.needed.shape[0]
    g_safe = jnp.where(gang.group >= 0, gang.group, GR)
    hit = (feasible & pods.valid).astype(jnp.int32)
    return jnp.zeros((GR + 1,), jnp.int32).at[g_safe].add(hit)[:GR]


def assign_gang(
    tables: ClusterTables,
    cyc: CycleArrays,
    pods: PodArrays,
    init: AssignState,
    gang: GangArrays,
    max_waves: int | None = None,
    soft_rounds: int = 4,
    engine_fn=None,
    return_waves: bool = False,
) -> tuple[AssignResult, GangVerdict]:
    """Wave assignment with group-atomic admission. Returns the result plus
    the GangVerdict (host surfaces per-group events from it).
    Pods of rejected groups come back node=-1/infeasible.

    engine_fn(tables, cyc, pods, init) -> AssignResult lets the literal
    scan (ops/assign.py assign_batch, the executable spec) drive the
    feasibility loop instead of the wave engine, the default."""
    GR = gang.needed.shape[0]
    P = pods.valid.shape[0]

    def run(rejected: Array):
        ok = (gang.group < 0) | ~rejected[jnp.clip(gang.group, 0, GR - 1)]
        masked = pods._replace(valid=pods.valid & ok)
        if engine_fn is not None:
            res = engine_fn(tables, cyc, masked, init)
            waves = jnp.full((P,), -1, jnp.int32)
        else:
            res, waves = assign_waves(tables, cyc, masked, init, max_waves,
                                      return_waves=True)
        placed = _placed_per_group(gang, masked, res.feasible)
        under = gang.valid & ~rejected & (placed < gang.needed)
        return res, waves, under, placed

    def cond(c: _GangCarry) -> Array:
        # rounds==0 is the unconditional first run; afterwards loop while
        # any group is underfilled (each round rejects ≥1, cap GR+2)
        return (c.rounds == 0) | (c.under.any() & (c.rounds < GR + 2))

    def body(c: _GangCarry) -> _GangCarry:
        # zero-placed underfilled groups hold NOTHING: excluding them frees
        # no capacity, so no OTHER group's fill depends on them — reject
        # them all at once (collapses statically-infeasible jobs into one
        # extra round; a zero-placed group that might have filled after a
        # partial rejection simply retries next cycle via the queue, the
        # same deferral the Permit-timeout path gives it). PARTIALLY-filled
        # groups do hold capacity; release them one per round (lowest rank
        # first) so survivors absorb the freed space — until soft_rounds,
        # after which the remaining tail rejects in bulk. The first round
        # (rounds==0, dummy carry) rejects nothing.
        zero = c.under & (c.placed == 0)
        partial = c.under & (c.placed > 0)
        worst = jnp.argmax(jnp.where(partial, gang.rank, -1))
        one = jnp.zeros((GR,), bool).at[worst].set(True) & partial
        newly = zero | jnp.where(c.rounds > soft_rounds, partial, one)
        newly = newly & (c.rounds > 0)
        rejected = c.rejected | newly
        short = jnp.where(newly, c.placed, c.short)
        res, waves, under, placed = run(rejected)
        return _GangCarry(rejected=rejected, short=short, under=under,
                          placed=placed,
                          rounds=c.rounds + 1, node=res.node,
                          feasible=res.feasible, waves=waves, state=res.state)

    # ONE instance of the wave fixpoint in the program: an unrolled initial
    # run plus the loop body doubled the compiled graph, which at
    # 5k nodes × 100k pods × 3.5k classes was enough to take the TPU
    # worker down; the dummy init carry (under=True, rounds=0) makes the
    # first loop iteration BE the initial run instead.
    final = lax.while_loop(cond, body, _GangCarry(
        rejected=jnp.zeros((GR,), bool),
        short=jnp.zeros((GR,), jnp.int32),
        under=jnp.ones((GR,), bool),
        placed=jnp.zeros((GR,), jnp.int32),
        rounds=jnp.int32(0),
        node=jnp.full((P,), -1, jnp.int32),
        feasible=jnp.zeros((P,), bool),
        waves=jnp.full((P,), -1, jnp.int32),
        state=init))

    # the loop always exits with `under` empty (after the initial round,
    # each iteration rejects ≥1 group; rounds cap at GR+2 counting the
    # dummy-carry first iteration); the strip below also covers the
    # unreachable cap exit
    dead = final.rejected | final.under
    ok = (gang.group < 0) | ~dead[jnp.clip(gang.group, 0, GR - 1)]
    result = AssignResult(node=jnp.where(ok, final.node, -1),
                          feasible=final.feasible & ok, state=final.state)
    verdict = GangVerdict(
        rejected=dead & gang.valid,
        placed=jnp.where(final.rejected, final.short, final.placed),
        rounds=final.rounds,
        groups=gang.valid.sum(dtype=jnp.int32))
    if return_waves:
        return result, verdict, final.waves
    return result, verdict
