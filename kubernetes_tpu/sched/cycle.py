"""The scheduling-cycle driver: host objects in, placements out.

Replaces the reference's per-pod loop (scheduler.go:596-763 scheduleOne →
generic_scheduler.go:187 Schedule) with one batched device dispatch per cycle:
encode/patch state → build the per-cycle lattice (PreFilter/metadata analog) →
run the assignment scan → read back placements.

Compilation is cached per Dims signature (capacities bucket to powers of two,
state/dims.py), so steady-state cycles pay one dispatch, zero recompiles.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..api.types import Node, Pod
from ..ops.assign import assign_batch, initial_state
from ..ops.lattice import build_cycle, default_engine_config
from ..state.arrays import ClusterTables, PodArrays
from ..state.dims import Dims
from ..state.encode import Encoder
from .telemetry import xla_scope

UNSCHEDULABLE_TAINT_KEY = "node.kubernetes.io/unschedulable"  # predicates.go:1522-1541


def snapshot_with_keys(cache, encoder: Encoder, pending, base_dims,
                       device=None, mesh=None):
    """Snapshot + the interned synthetic-taint key ids every device dispatch
    needs — the single home for the UNSCHEDULABLE_TAINT_KEY interning ritual
    (shared by the scheduler wave path and the extender backend). `device`
    routes the arrays to an explicit placement (the supervisor's degraded
    mode: everything onto the CPU fallback, nothing on the lost backend);
    `mesh` routes them to mesh-resident sharded placement instead (the live
    multichip serving path — state/cache.py keeps the tables resident).
    What it compiles (a fresh patch rung, an eager scalar) is the XLA
    account's under `snapshot`, whoever waits being the enclosing scope's
    (sched/telemetry.py)."""
    with xla_scope("snapshot"):
        snap = cache.snapshot(encoder, pending, base_dims,
                              extra_intern=(UNSCHEDULABLE_TAINT_KEY,),
                              device=device, mesh=mesh)
        return snap, _taint_scalars(encoder, device, mesh)


def micro_snapshot_with_keys(cache, encoder: Encoder, pending, base_dims,
                             micro_p: int, device=None, mesh=None):
    """Micro-wave snapshot (ISSUE 18): bring the RESIDENT cluster state
    current through the ordinary generation-diffed snapshot — with an
    EMPTY pending batch, so node/existing-pod deltas ride the same
    patch/donation machinery as a bulk wave — then graft a small
    standalone [micro_p] pending block holding just the watch-delta pods
    (state/cache.py micro_graft). The pods are interned FIRST so any
    registry/capacity growth they cause lands in the base snapshot's
    dims/tables before the graft reads them. Flipping micro↔bulk changes
    only the pending identity signature, so each direction's first
    snapshot after a flip rebuilds one pending block and nothing else."""
    encoder.intern_pods(pending)
    with xla_scope("snapshot"):
        base = cache.snapshot(encoder, [], base_dims,
                              extra_intern=(UNSCHEDULABLE_TAINT_KEY,),
                              device=device, mesh=mesh)
        snap = cache.micro_graft(encoder, pending, base, micro_p,
                                 device=device, mesh=mesh)
        return snap, _taint_scalars(encoder, device, mesh)


def _taint_scalars(encoder: Encoder, device, mesh):
    """The interned synthetic-taint scalar pair every dispatch carries.
    The scalars are created ON the routed placement — a jnp constructor
    on the default (possibly dead) backend is exactly what degraded mode
    must never touch, and a single-device scalar next to mesh-resident
    tables would force GSPMD to re-commit it every dispatch."""
    encoder.vocabs.label_vals.intern("")
    import contextlib

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        uk = jax.device_put(
            jnp.int32(encoder.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY)),
            rep)
        ev = jax.device_put(jnp.int32(encoder.vocabs.label_vals.get("")), rep)
        return uk, ev
    ctx = jax.default_device(device) if device is not None \
        else contextlib.nullcontext()
    with ctx:
        uk = jnp.int32(encoder.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY))
        ev = jnp.int32(encoder.vocabs.label_vals.get(""))
    return uk, ev


def plan_engine(has_node_name: bool) -> str:
    """The program one wave dispatches, and the engine its prewarm /
    supervisor key names — the single home of the choice. 'waves'
    (ops/waves.py, wave-parallel dense admission) serves; a
    nodeName-bearing batch goes to 'scan' (ops/assign.py, the literal
    sequential-assume lax.scan that is also the executable spec the tests
    hold 'waves' to): spec.nodeName is a per-POD (not per-class) host
    constraint the class-granular wave path cannot express, and in the
    reference such pods bypass the scheduler entirely (kubelet consumes
    them), so a batch containing one is rare. The flag comes from Dims
    (computed host-side at encode time) so the hot path never blocks on a
    device readback."""
    return "scan" if has_node_name else "waves"


def _apply_extra_plugins(tables, cyc, extra_plugins, extra_weights):
    """Fold configured out-of-set score plugins (NodeLabel, RTCR, …) into the
    static score lattice as a per-class bias — the fused-path analog of
    RunScorePlugins for plugins EngineConfig has no fixed slot for. They are
    evaluated against a per-CLASS identity pending view (their scores are
    class-pure)."""
    if not extra_plugins:
        return cyc
    from ..framework.interface import CycleState, TensorContext

    classes = tables.classes
    SC = classes.valid.shape[0]
    ident = PodArrays(
        valid=classes.valid,
        name_id=jnp.full((SC,), -1, jnp.int32),
        ns=classes.ns,
        cls=jnp.arange(SC, dtype=jnp.int32),
        priority=jnp.zeros((SC,), jnp.int32),
        creation=jnp.zeros((SC,), jnp.int32),
        node_id=jnp.full((SC,), -1, jnp.int32),
        node_name_req=jnp.full((SC,), -1, jnp.int32),
        pin=jnp.full((SC,), -1, jnp.int32),
    )
    ctx = TensorContext(tables=tables, cyc=cyc, pending=ident)
    bias = jnp.zeros_like(cyc.static.score)
    for pl, w in zip(extra_plugins, extra_weights):
        bias = bias + jnp.asarray(w, jnp.float32) * pl.score_matrix(
            CycleState(), ctx).astype(jnp.float32)
    return cyc._replace(static=cyc.static._replace(
        score=cyc.static.score + bias))


@functools.partial(jax.jit, static_argnums=(3, 5, 8, 11, 12))
def _schedule_batch_impl(
    tables: ClusterTables,
    pending: PodArrays,
    keys: Tuple[jnp.ndarray, jnp.ndarray],
    D: int,
    existing: PodArrays,
    engine: str,
    hard_weight=1.0,
    ecfg=None,
    extra_plugins: tuple = (),
    extra_weights: tuple = (),
    gang=None,
    return_waves: bool = False,
    explain: bool = False,
):
    from ..ops.gang import assign_gang
    from ..ops.waves import assign_waves

    uk, ev = keys
    # stage names for the profiler's name-scope line (metadata only)
    with jax.named_scope("build_cycle"):
        cyc = build_cycle(tables, existing, uk, ev, D, hard_weight, ecfg)
        cyc = _apply_extra_plugins(tables, cyc, extra_plugins, extra_weights)
        init = initial_state(tables, cyc)
    waves = None
    if gang is not None:
        # group-atomic admission (ops/gang.py); gang=None traces the plain
        # engines, so gang-free batches compile/run exactly as before
        if return_waves and engine == "waves":
            res, verdict, waves = assign_gang(
                tables, cyc, pending, init, gang, return_waves=True)
        else:
            res, verdict = assign_gang(
                tables, cyc, pending, init, gang,
                engine_fn=assign_batch if engine == "scan" else None)
        res = res._replace(gang=verdict)
    elif engine == "scan":
        res = assign_batch(tables, cyc, pending, init)
    elif return_waves:
        # bench/profiling: per-pod admission-wave indices ride along so the
        # driver can report wave counts without a second dispatch
        res, waves = assign_waves(tables, cyc, pending, init,
                                  return_waves=True)
    else:
        res = assign_waves(tables, cyc, pending, init)
    if explain:
        # decision provenance (ISSUE 10): the attribution reduction runs
        # INSIDE this same dispatch, against the post-wave assume state.
        # The scan engine attributes per pod (the spec); the class-interned
        # wave engine attributes once per equivalence class and fans out.
        # A static flag: explain=False traces the byte-for-byte
        # pre-provenance program.
        from ..ops.assign import explain_assignments

        with jax.named_scope("explain"):
            exp = explain_assignments(
                tables, cyc, pending, res,
                granularity="pod" if engine == "scan" else "class")
        return res, exp
    return (res, waves) if return_waves else res


def _schedule_batch(tables, pending, keys, D, existing,
                    has_node_name: bool = False,
                    hard_weight: float = 1.0,
                    ecfg=None,
                    extra_plugins: tuple = (),
                    extra_weights: tuple = (),
                    gang=None,
                    return_waves: bool = False,
                    dims=None,
                    prewarmer=None,
                    mesh=None,
                    explain: bool = False,
                    engine: Optional[str] = None):
    # the two opt-in result tails are mutually exclusive by contract:
    # return_waves callers unpack (res, waves) and would silently read an
    # ExplainResult as the wave-index array
    assert not (explain and return_waves), \
        "explain and return_waves cannot be combined"
    # a wave passes the engine it keyed its prewarm and supervisor budget
    # on; a direct caller (tests, bench) passes none and gets the same one
    if engine is None:
        engine = plan_engine(has_node_name)
    # hardPodAffinitySymmetricWeight (apis/config/types.go:70) and the
    # EngineConfig plugin composition ride as traced f32 scalars so config
    # changes never recompile
    from ..ops.lattice import strong_engine_config

    ecfg = strong_engine_config(ecfg) if ecfg is not None \
        else default_engine_config()
    hw = jnp.float32(hard_weight)
    # explain bypasses the prewarmed executables: they were AOT-compiled
    # without the attribution tail, and a separate explain-keyed compile
    # set would double the prewarm budget for an opt-in debug surface —
    # the module-level jit cache keeps explain-on steady state warm instead
    if prewarmer is not None and dims is not None and not return_waves \
            and not explain:
        # prewarmed executable for this exact signature: calling the stored
        # jax Compiled skips trace+lower+compile — the boundary cycle right
        # after a capacity-bucket crossing stays in budget (sched/prewarm.py).
        # The key carries the MESH signature: a mesh-sharded program and a
        # single-device one at the same Dims are different executables, and
        # invoking one with the other's arrays would silently reshard onto
        # (possibly dead) devices — lookup isolation makes that impossible.
        compiled = prewarmer.lookup(dims, engine, extra_plugins,
                                    gang is not None, mesh=mesh)
        if compiled is not None:
            ok, out = prewarmer.call(compiled, tables, pending, keys,
                                     existing, hw, ecfg, extra_weights, gang)
            if ok:
                return out
    return _schedule_batch_impl(tables, pending, keys, D, existing, engine,
                                hw, ecfg,
                                extra_plugins, extra_weights, gang,
                                return_waves, explain)


@functools.partial(jax.jit, static_argnums=(3,))
def _feasible(
    tables: ClusterTables,
    pending: PodArrays,
    keys: Tuple[jnp.ndarray, jnp.ndarray],
    D: int,
    existing: PodArrays,
    hard_weight=1.0,
    ecfg=None,
) -> jnp.ndarray:
    """[P, N] Filter mask — findNodesThatFit as one dispatch (golden tests,
    extender Filter verb)."""
    from ..ops.assign import feasible_matrix

    uk, ev = keys
    cyc = build_cycle(tables, existing, uk, ev, D, hard_weight,
                      ecfg or default_engine_config())
    return feasible_matrix(tables, cyc, pending)


@functools.partial(jax.jit, static_argnums=(3, 7))
def _scores(
    tables: ClusterTables,
    pending: PodArrays,
    keys: Tuple[jnp.ndarray, jnp.ndarray],
    D: int,
    existing: PodArrays,
    hard_weight=1.0,
    ecfg=None,
    extra_plugins: tuple = (),
    extra_weights: tuple = (),
) -> jnp.ndarray:
    """[P, N] Score matrix — prioritizeNodes as one dispatch (extender
    Prioritize verb, golden tests). Same composition as the batch path,
    including configured out-of-set plugins."""
    from ..ops.assign import score_matrix

    uk, ev = keys
    cyc = build_cycle(tables, existing, uk, ev, D, hard_weight,
                      ecfg or default_engine_config())
    cyc = _apply_extra_plugins(tables, cyc, extra_plugins, extra_weights)
    return score_matrix(tables, cyc, pending)


@functools.partial(jax.jit, static_argnums=(3,))
def _diagnose(
    tables: ClusterTables,
    pending: PodArrays,
    keys: Tuple[jnp.ndarray, jnp.ndarray],
    D: int,
    existing: PodArrays,
    hard_weight=1.0,
    ecfg=None,
):
    """Per-predicate [P, N] component masks (PredicateFailureReason analog) —
    module-level jit so repeated extender Filter calls hit the compile cache."""
    from ..ops.assign import mask_components

    uk, ev = keys
    cyc = build_cycle(tables, existing, uk, ev, D, hard_weight,
                      ecfg or default_engine_config())
    return mask_components(tables, cyc, pending)


@functools.partial(jax.jit, static_argnums=(3, 7))
def _evaluate(
    tables: ClusterTables,
    pending: PodArrays,
    keys: Tuple[jnp.ndarray, jnp.ndarray],
    D: int,
    existing: PodArrays,
    hard_weight=1.0,
    ecfg=None,
    extra_plugins: tuple = (),
    extra_weights: tuple = (),
):
    """`(mask [P, N], MaskComponents, scores [P, N])` from ONE lattice: what
    `_feasible`, `_diagnose` and `_scores` give, each of which rebuilds the
    cycle for itself, as one dispatch (the extender's verbs: a pod is
    evaluated once and both its answers are cut from these arrays). The
    three stay as the spec this is held to (tests/test_extender.py)."""
    from ..ops.assign import feasible_matrix, mask_components, score_matrix

    uk, ev = keys
    cyc = build_cycle(tables, existing, uk, ev, D, hard_weight,
                      ecfg or default_engine_config())
    scored = _apply_extra_plugins(tables, cyc, extra_plugins, extra_weights)
    return (feasible_matrix(tables, cyc, pending),
            mask_components(tables, cyc, pending),
            score_matrix(tables, scored, pending))


@dataclass
class CycleResult:
    """Placements for one cycle. `assignments[i]` is the node name for
    pending[i], or None if unschedulable (FitError analog)."""

    assignments: List[Optional[str]]
    scheduled: int
    failed: int


class BatchScheduler:
    """Stateless-per-call batch scheduler: give it the world, get placements.

    This is the core 'algorithm' object (genericScheduler analog). The stateful,
    watch-driven incremental path lives in sched/scheduler.py on top of
    state/cache.py."""

    def __init__(self) -> None:
        self.encoder = Encoder()

    def schedule(
        self,
        nodes: Sequence[Node],
        existing: Sequence[Pod],
        pending: Sequence[Pod],
        base_dims: Optional[Dims] = None,
    ) -> CycleResult:
        enc = self.encoder
        # the synthetic unschedulable taint must be interned before matching
        enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
        enc.vocabs.label_vals.intern("")
        tables, ex, pe, d = enc.encode_cluster(nodes, existing, pending, base_dims)

        uk = jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY))
        ev = jnp.int32(enc.vocabs.label_vals.get(""))
        bound: Dict[int, int] = {}
        for p in existing:
            g = enc.group_id(p)
            if g >= 0:
                bound[g] = bound.get(g, 0) + 1
        gang = enc.build_gang_arrays(list(pending), d, bound)
        res = _schedule_batch(
            jax.device_put(tables), jax.device_put(pe), (uk, ev), d.D,
            jax.device_put(ex), has_node_name=d.has_node_name, gang=gang,
        )
        node_idx = jax.device_get(res.node)

        assignments: List[Optional[str]] = []
        scheduled = failed = 0
        for i, p in enumerate(pending):
            ni = int(node_idx[i])
            if ni >= 0:
                assignments.append(nodes[ni].name)
                scheduled += 1
            else:
                assignments.append(None)
                failed += 1
        return CycleResult(assignments=assignments, scheduled=scheduled, failed=failed)
