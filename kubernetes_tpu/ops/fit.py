"""NodeResourcesFit and resource-based scores as tensor ops.

Reference semantics: PodFitsResources (algorithm/predicates/predicates.go:789-845)
— the pod-count check used+1 ≤ allowedPodNumber always applies; then, UNLESS the
pod requests zero of everything (the fast path :800-806), every resource must
satisfy request_r ≤ allocatable_r − used_r. Note the asymmetry this implies on
overcommitted nodes: a pod requesting 0 memory still FAILS if memory free is
negative (Go: 0 > negative ⇒ insufficient), but an all-zero pod passes — found
by the randomized golden tests, not obvious from the prose.

Scores: least_requested.go / most_requested.go / balanced_resource_allocation.go.
The reference computes integer (cap−total)*100/cap per resource; we compute in
float32 (memory capacities exceed int32×100), which can differ from the
reference by <1 score point — masks stay bit-exact, scores are within ±1.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..api.types import NUM_FIXED_RES, RES_CPU, RES_MEM, RES_PODS
from ..state.arrays import Array, NodeArrays, ReqTable

MAX_NODE_SCORE = 100.0  # framework/v1alpha1/interface.go:87


def _fit(vec: Array, free: Array) -> Array:
    """vec: [..., R], free: [..., R] → [...] bool per PodFitsResources.

    Asymmetry of the reference (predicates.go:800-845): cpu/mem/ephemeral are
    checked even when the pod requests 0 of them (0 > negative-free fails on an
    overcommitted node), but *scalar* resources are only checked when requested
    (Go iterates podRequest.ScalarResources), so a zero scalar request passes
    regardless of that scalar's free. Oracle: api/semantics.py pod_fits_resources."""
    R = vec.shape[-1]
    idx = jnp.arange(R)
    is_pods = idx == RES_PODS
    is_scalar = idx >= NUM_FIXED_RES
    pods_ok = (jnp.where(is_pods, vec, 0) <= jnp.where(is_pods, free, 0)).all(-1)
    zero_all = jnp.where(is_pods, 0, vec).max(-1) == 0
    res_ok = (is_pods | (is_scalar & (vec == 0)) | (vec <= free)).all(-1)
    return pods_ok & (zero_all | res_ok)


def fit_matrix(reqs: ReqTable, nodes: NodeArrays) -> Array:
    """[SR, N] bool: request-class r fits on node n given current `used`."""
    free = nodes.alloc - nodes.used  # [N, R]
    return _fit(reqs.vec[:, None, :], free[None, :, :]) & nodes.valid[None, :]


def fit_row(req_vec: Array, used: Array, alloc: Array, valid: Array) -> Array:
    """[N] bool for one request vector against live used — the scan inner check."""
    return _fit(req_vec[None, :], alloc - used) & valid


def _frac(total: Array, cap: Array) -> Array:
    cap_f = cap.astype(jnp.float32)
    return jnp.where(cap > 0, total.astype(jnp.float32) / jnp.maximum(cap_f, 1.0), 0.0)


def resource_scores_row(
    req_vec: Array, used: Array, alloc: Array
) -> tuple[Array, Array, Array]:
    """(least_requested [N], balanced_allocation [N], most_requested [N]) in
    0..100 float32.

    least_requested.go:60-77: per-resource (cap−total)*100/cap clamped at 0,
    averaged over cpu+memory. balanced_resource_allocation.go:68-102:
    100 − |cpuFraction−memFraction|*100, 0 if either fraction ≥ 1.
    most_requested.go:52-70: total*100/cap averaged (bin packing; weight 0 in
    the default provider, enabled via config EngineConfig.w_most)."""
    total = used + req_vec[None, :]  # [N, R]
    cpu_cap, mem_cap = alloc[:, 0], alloc[:, 1]
    cpu_t, mem_t = total[:, 0], total[:, 1]

    def least(t, cap):
        s = (cap.astype(jnp.float32) - t.astype(jnp.float32)) * MAX_NODE_SCORE
        s = s / jnp.maximum(cap.astype(jnp.float32), 1.0)
        return jnp.where((cap > 0) & (t <= cap), s, 0.0)

    def most(t, cap):
        s = t.astype(jnp.float32) * MAX_NODE_SCORE \
            / jnp.maximum(cap.astype(jnp.float32), 1.0)
        return jnp.where((cap > 0) & (t <= cap), s, 0.0)

    least_score = (least(cpu_t, cpu_cap) + least(mem_t, mem_cap)) / 2.0
    most_score = (most(cpu_t, cpu_cap) + most(mem_t, mem_cap)) / 2.0

    cf, mf = _frac(cpu_t, cpu_cap), _frac(mem_t, mem_cap)
    balanced = jnp.where(
        (cf >= 1.0) | (mf >= 1.0), 0.0, MAX_NODE_SCORE - jnp.abs(cf - mf) * MAX_NODE_SCORE
    )
    return least_score, balanced, most_score


# upstream's non-zero defaults (priorities/util/non_zero.go): what a pod that
# asks no cpu / no memory counts for in a resource SCORE. KiB here.
DEFAULT_MILLI_CPU = 100
DEFAULT_MEMORY_KIB = 200 * 1024


def _pct_floor(num: Array, den: Array) -> Array:
    """floor(100 * num / den) for 0 <= num <= den < 2**31, exactly, in
    uint32: memory in KiB x 100 leaves int32, and the reference's int64
    division is what the score is held to. Long division by the bits of 100,
    most significant first; `m * num = q * den + rem` holds after each."""
    num = num.astype(jnp.uint32)
    den = den.astype(jnp.uint32)
    q = jnp.zeros_like(num)
    rem = jnp.zeros_like(num)
    for bit in (1, 1, 0, 0, 1, 0, 0):
        rem = rem * 2
        over = rem >= den
        rem = jnp.where(over, rem - den, rem)
        q = q * 2 + over
        if bit:
            rem = rem + num
            over = rem >= den
            rem = jnp.where(over, rem - den, rem)
            q = q + over
    return q.astype(jnp.int32)


def broken_linear(p: Array, xs: Array, ys: Array) -> Array:
    """buildBrokenLinearFunction (requested_to_capacity_ratio.go): the value
    at utilization `p` (i32, any shape) of the function through the points
    (xs [K] ascending, ys [K]; a tail of repeats of the last point is
    inert), flat outside them, each segment in the reference's truncating
    integer arithmetic. The segment `p` falls in is picked by selects, so
    that ONE integer division an element is left (the chip has none in
    hardware)."""
    K = xs.shape[0]
    x0, y0 = xs[0], ys[0]
    dx = dy = jnp.int32(0)
    for i in range(1, K):
        at = (p > xs[i - 1]) & (p <= xs[i])
        x0 = jnp.where(at, xs[i - 1], x0)
        y0 = jnp.where(at, ys[i - 1], y0)
        dx = jnp.where(at, xs[i] - xs[i - 1], dx)
        dy = jnp.where(at, ys[i] - ys[i - 1], dy)
    inside = y0 + jax.lax.div(dy * (p - x0), jnp.maximum(dx, 1))
    return jnp.where(p <= xs[0], ys[0],
                     jnp.where(p > xs[K - 1], ys[K - 1], inside))


def rtc_score_row(req_vec: Array, used: Array, alloc: Array,
                  rtc_x: Array, rtc_y: Array, rtc_w: Array) -> Array:
    """RequestedToCapacityRatio [N], 0..100 as float32 of an integer.

    requested_to_capacity_ratio.go (v1.17), over the resources r whose
    weight w_r in `rtc_w` (one a slot of the R axis, 0 = not in the map) is
    positive: `util_r = 100 - (cap - total) * 100 / cap` in integers, 100
    where the node has none of r or `total > cap`; `total = used + req`, a
    pod that asks no cpu / no memory counting the non-zero defaults;
    `s_r = shape(util_r)` (broken_linear); `round(sum w_r s_r / sum w_r)`
    over the resources with `s_r > 0`, 0 if none. `used` is the engine's
    live plane, so a round's own placements move the score, as
    LeastAllocated's and MostAllocated's.

    A resource at a time, each an [N] column: vectors along the node axis
    fill the chip's lanes, where an [N, R] plane with R (4-16) as its minor
    axis leaves most of them empty (measured, PR 53: the plane form cost
    0.2-1 ms a round, most of what the round's other scores cost together)."""
    xs, ys = rtc_x.astype(jnp.int32), rtc_y.astype(jnp.int32)
    num = den = jnp.int32(0)
    for r in range(min(req_vec.shape[-1], rtc_w.shape[0])):
        w = rtc_w[r].astype(jnp.int32)
        req = req_vec[r]
        if r == RES_CPU:
            req = jnp.where(req == 0, DEFAULT_MILLI_CPU, req)
        if r == RES_MEM:
            req = jnp.where(req == 0, DEFAULT_MEMORY_KIB, req)
        cap, total = alloc[:, r], used[:, r] + req
        full = (cap <= 0) | (total > cap)
        util = jnp.where(full, 100, 100 - _pct_floor(
            jnp.where(full, 0, cap - total), jnp.maximum(cap, 1)))
        s = broken_linear(util, xs, ys)
        counted = (w > 0) & (s > 0)
        num = num + jnp.where(counted, s * w, 0)
        den = den + jnp.where(counted, w, 0)
    # math.Round of a non-negative quotient: half up
    score = (2 * num + den) // jnp.maximum(2 * den, 1)
    return jnp.where(den > 0, score, 0).astype(jnp.float32)
