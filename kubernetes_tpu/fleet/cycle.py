"""The fleet tick: K tenant cycles as ONE vmap'd XLA dispatch.

The single-cluster cycle body (ops/lattice.py build_cycle → assignment
engine, the exact sequence `sched/cycle.py:_schedule_batch_impl` traces) is
vmapped over the leading tenant axis of the stacked tables. Tenants are
independent by construction — no collective crosses the tenant axis — so on
a tenant-axis mesh (parallel/mesh.py TENANT_AXIS) each chip evaluates its
own tenants and the dispatch count per tick is exactly one, which is the
budget the fleet bench stage enforces (`fleet_dispatches_per_tick=1`).

The DRF quota clamp (fleet/quota.py) runs INSIDE the same program — a pure
pre-mask on `pending.valid` — so quota enforcement costs no extra dispatch
and per-tenant placements stay bit-equal to a solo run under the same clamp
(vmap of these engines is element-wise exact; the bit-equality suite in
tests/test_fleet.py holds the line).

Engines: the server's tick dispatches 'waves'; `engine` is the prewarm
key's engine slot, and 'scan' traces the executable spec under the same vmap.
Gang-bearing and nodeName-bearing tenant batches are
NOT vmapped (group-atomic admission runs host rejection rounds; a pin would
serialize every tenant); the server routes those tenants through their own
single-cluster wave.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.assign import assign_batch, initial_state
from ..ops.lattice import build_cycle, default_engine_config
from .quota import drf_admission_row

Array = jnp.ndarray


class FleetResult(NamedTuple):
    """One fleet tick's device outputs, all [K, …]."""

    node: Array      # [K, P] i32 chosen node row per tenant, -1 none
    feasible: Array  # [K, P] bool
    admitted: Array  # [K, P] bool — the DRF pre-mask (valid ∧ under-quota);
                     # valid ∧ ¬admitted pods were quota-clamped this tick
                     # (requeue promptly, no failure verdict)
    share: Array     # [K] f32 pre-tick dominant share per tenant
    dom: Array       # [K, P] f32 per-pod dominant demand (violation check)


def fleet_signature(K: int) -> int:
    """The tenant-stack signature that flows into every prewarm executable
    key (sched/prewarm.py `fleet=` slot): the padded stack width. Presence
    alone isolates fleet Compileds from single-cluster ones."""
    return int(K)


@functools.partial(jax.jit, static_argnums=(3, 5, 9))
def _fleet_cycle_impl(
    tables,          # stacked ClusterTables [K, …]
    pending,         # stacked PodArrays [K, P]
    keys,            # (uk [K], ev [K]) per-tenant interned taint-key ids
    D: int,
    existing,        # stacked PodArrays [K, E]
    engine: str,
    quota,           # [K] f32 DRF quota fraction per tenant
    hard_weight=1.0,
    ecfg=None,
    explain: bool = False,
):
    from ..ops.waves import assign_waves

    def body(t, pe, ky, ex, q):
        uk, ev = ky
        cyc = build_cycle(t, ex, uk, ev, D, hard_weight, ecfg,
                          copies=quota.shape[0])
        admitted, share, dom = drf_admission_row(t, pe, q)
        clamped = pe._replace(valid=admitted)
        init = initial_state(t, cyc)
        if engine == "scan":
            res = assign_batch(t, cyc, clamped, init)
        else:
            res = assign_waves(t, cyc, clamped, init)
        exp = None
        if explain:
            # ISSUE 10: fleet mode attributes PER TENANT inside the same
            # vmap'd dispatch — the class-collapsed reduction per tenant
            # row (quota-clamped pods carry valid=False and zero out; the
            # commit loop requeues them before ever reading attribution)
            from ..ops.assign import explain_assignments

            exp = explain_assignments(t, cyc, clamped, res,
                                      granularity="class")
        return res.node, res.feasible, admitted, share, dom, exp

    node, feas, admitted, share, dom, exp = jax.vmap(body)(
        tables, pending, keys, existing, quota)
    res = FleetResult(node=node, feasible=feas, admitted=admitted,
                      share=share, dom=dom)
    return (res, exp) if explain else res


def dispatch_fleet(tables, pending, keys, D, existing, engine, quota,
                   hard_weight: float = 1.0, ecfg=None,
                   dims=None, prewarmer=None, mesh=None,
                   explain: bool = False):
    """The fleet analog of sched/cycle.py `_schedule_batch`: normalize the
    traced config scalars, probe the prewarmer for an AOT executable under
    the FLEET key (dims, engine, fleet=K, mesh) — a single-cluster
    Compiled can never answer, the key slot forbids it — and fall through
    to the ordinary jit. With `explain` (ISSUE 10, KTPU_EXPLAIN) the
    prewarmed executables are bypassed (they were compiled without the
    attribution tail) and the result is (FleetResult, stacked [K, …]
    ExplainResult)."""
    from ..ops.lattice import strong_engine_config

    K = int(quota.shape[0])
    ecfg = strong_engine_config(ecfg) if ecfg is not None \
        else default_engine_config()
    hw = jnp.float32(hard_weight)
    if prewarmer is not None and dims is not None and not explain:
        compiled = prewarmer.lookup(dims, engine, (), False, mesh=mesh,
                                    fleet=fleet_signature(K))
        if compiled is not None:
            ok, out = prewarmer.call(compiled, tables, pending, keys,
                                     existing, quota, hw, ecfg)
            if ok:
                return FleetResult(*out)
    return _fleet_cycle_impl(tables, pending, keys, D, existing, engine,
                             quota, hw, ecfg, explain)
