"""Compile-ahead for capacity-bucket growth: kill the cold-compile cliff.

Capacities bucket to coarse shapes (state/dims.py) so steady-state cycles hit
one compiled program — but CROSSING a bucket (cluster grows past 2,048 nodes,
existing pods double past E) swaps the shape signature and pays a fresh XLA
compile, which at 2k+ nodes is minutes (BENCH_r03: 106 s at the 2k×20k
bucket). In a live cluster that is a scheduling stall at exactly the moment
the cluster is growing.

The fix is the same trick ahead-of-time-compiled systems use: when occupancy
of a growing axis crosses `threshold` (default 80%), a background thread
AOT-compiles the NEXT bucket's program from abstract shapes only —
`jit(...).lower(ShapeDtypeStructs).compile()` needs no real arrays and no
device dispatch. The persistent compilation cache (utils/platform.py
enable_compile_cache) is keyed by the HLO, so when the live path first calls
with the new shapes it deserializes the already-built executable (~seconds)
instead of compiling (~minutes). The scheduler keeps cycling on the current
bucket the whole time; nothing blocks.

The reference needs no analog (Go is AOT-compiled; its scheduler has no
shape-specialized programs) — this is pure XLA-runtime plumbing, documented
in docs/PERF.md.
"""

from __future__ import annotations

import threading
from dataclasses import replace
from typing import Callable, Optional

from ..state.dims import Dims
from .telemetry import xla_scope

#: the parts of `_cycle_key` / `_preempt_key`: what the XLA account names a
#: background compile's signature by (sched/telemetry.py `XlaAccount.scope`)
_CYCLE_KEY_NAMES = ("dims", "engine", "extras", "gang", "fleet", "mesh")
_PREEMPT_KEY_NAMES = ("program", "dims", "burst", "mesh")

# axes that grow monotonically in a live cluster and cross buckets: nodes,
# bound pods. (P — the pending batch — is bounded by batch_size and churns
# rather than grows.)
_GROWTH_AXES = ("N", "E")


def _abstract_tables(tables, mesh):
    """(abstract ClusterTables, replicated-sharding-or-None) — the shared
    half of abstract_cycle_args / abstract_preempt_args. With a mesh, the
    node tables carry the node-axis NamedShardings and everything else the
    replicated one, so both AOT paths compile the SAME GSPMD placement the
    live mesh path dispatches; layout changes live in parallel/mesh.py
    table_shardings, in exactly one place."""
    import jax

    if mesh is None:
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tables), None
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import table_shardings

    rep = NamedSharding(mesh, PartitionSpec())
    tsh = table_shardings(tables, mesh)
    abstract = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tables, tsh)
    return abstract, rep


def abstract_cycle_args(d: Dims, gang: bool = False, mesh=None):
    """ShapeDtypeStruct pytrees for one _schedule_batch_impl call at dims
    `d` — built from a throwaway Encoder's empty tables, so shapes/dtypes
    and pytree structure are BY CONSTRUCTION the ones the live path passes.
    `gang=True` adds abstract GangArrays (gang-bearing batches trace a
    structurally different program — the restart loop). `mesh` attaches the
    serving shardings (node axis split on the tables, everything else
    replicated — parallel/mesh.py), so the AOT compile produces the SAME
    GSPMD-partitioned executable the live mesh path dispatches."""
    import jax
    import jax.numpy as jnp

    from ..ops.gang import GangArrays
    from ..ops.lattice import abstract_engine_config
    from ..state.arrays import ClusterTables
    from ..state.encode import Encoder

    enc = Encoder()
    tables = ClusterTables(
        nodes=enc.empty_node_arrays(d),
        reqs=enc.build_req_table(d),
        labelsets=enc.build_labelset_table(d),
        nterms=enc.build_nterm_table(d),
        tolsets=enc.build_tolset_table(d),
        portsets=enc.build_portset_table(d),
        terms=enc.build_term_table(d),
        classes=enc.build_class_table(d),
        images=enc.build_image_table(d),
        zone_keys=enc.build_zone_keys(),
        volsets=enc.build_volset_table(d),
        drv_masks=enc.build_drv_masks(d),
    )
    pending = enc.build_pod_arrays([], d, capacity=d.P)
    existing = enc.build_pod_arrays([], d, capacity=d.E)
    abstract_tables, rep = _abstract_tables(tables, mesh)
    abstract = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), t)
    scalar_i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    scalar_f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    gang_args = None
    if gang:
        gang_args = GangArrays(
            group=jax.ShapeDtypeStruct((d.P,), jnp.int32, sharding=rep),
            needed=jax.ShapeDtypeStruct((d.GR,), jnp.int32, sharding=rep),
            valid=jax.ShapeDtypeStruct((d.GR,), jnp.bool_, sharding=rep),
            rank=jax.ShapeDtypeStruct((d.GR,), jnp.int32, sharding=rep),
        )
    return (abstract_tables, abstract(pending), (scalar_i32, scalar_i32),
            abstract(existing), scalar_f32,
            abstract_engine_config(rep),
            gang_args)


def abstract_preempt_args(d: Dims, burst: int, mesh=None):
    """ShapeDtypeStruct pytrees for one sched.preemption._preempt call at
    dims `d` with a preemptor burst of `burst` lanes — the preemption analog
    of abstract_cycle_args, so the burst program can compile in the
    background BEFORE the first preemption storm hits the live path. `mesh`
    attaches the serving shardings (the burst's what-if runs over the SAME
    mesh-resident tables as the wave cycle)."""
    import jax
    import jax.numpy as jnp

    from ..ops.lattice import abstract_engine_config
    from ..state.arrays import ClusterTables
    from ..state.encode import Encoder

    enc = Encoder()
    tables = ClusterTables(
        nodes=enc.empty_node_arrays(d),
        reqs=enc.build_req_table(d),
        labelsets=enc.build_labelset_table(d),
        nterms=enc.build_nterm_table(d),
        tolsets=enc.build_tolset_table(d),
        portsets=enc.build_portset_table(d),
        terms=enc.build_term_table(d),
        classes=enc.build_class_table(d),
        images=enc.build_image_table(d),
        zone_keys=enc.build_zone_keys(),
        volsets=enc.build_volset_table(d),
        drv_masks=enc.build_drv_masks(d),
    )
    existing = enc.build_pod_arrays([], d, capacity=d.E)
    abstract_tables, rep = _abstract_tables(tables, mesh)
    abstract = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), t)
    scalar_i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    scalar_f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    vec_i32 = jax.ShapeDtypeStruct((burst,), jnp.int32, sharding=rep)
    pdb = jax.ShapeDtypeStruct((d.E,), jnp.bool_, sharding=rep)
    return (abstract_tables, abstract(existing), vec_i32, vec_i32, vec_i32,
            (scalar_i32, scalar_i32), pdb, scalar_f32,
            abstract_engine_config(rep))


class BucketPrewarmer:
    """Watches per-cycle occupancy and compiles the next bucket ahead of
    need. One in-flight compile at a time; each (dims, engine) signature is
    warmed at most once per process.

    Compiled executables are KEPT (self.compiled) and the dispatch layer
    calls them directly (`sched/cycle.py _schedule_batch`): re-tracing the
    wave engine at a big shape costs seconds even with the persistent XLA
    cache, which would blow the boundary-cycle budget right when the
    cluster crosses a bucket. Calling the stored jax Compiled skips
    trace+lower+compile entirely — the first post-boundary cycle pays only
    the snapshot patch and the dispatch itself."""

    def __init__(self, threshold: float = 0.8, min_axis: int = 256,
                 compile_fn: Optional[Callable] = None):
        # min_axis: below this capacity a fresh compile is cheap enough that
        # warming would just burn test/laptop CPU — skip.
        # KTPU_PREWARM_MIN_AXIS overrides (small-shape bench validation).
        import os

        self.threshold = threshold
        self.min_axis = int(os.environ.get("KTPU_PREWARM_MIN_AXIS", min_axis))
        self.enabled = True   # bench/test gate: observe() is a no-op when off
        self._warmed: set = set()
        self._mu = threading.Lock()
        self._inflight: Optional[threading.Thread] = None
        # the preempt program warms on its OWN slot: a next-bucket cycle
        # compile can run for the better part of a minute, and serializing
        # behind it would leave the first preemption storm paying the
        # burst compile synchronously (XLA compiles release the GIL, so
        # two background compiles genuinely overlap)
        self._inflight_preempt: Optional[threading.Thread] = None
        self._compile_fn = compile_fn or self._compile
        self.warm_log: list = []   # (dims, engine) actually compiled — tests
        # (dims, engine, extras, gang, fleet, mesh sig) → jax Compiled
        # for the cycle program (fleet = the tenant-stack count K of a
        # fleet/cycle.py program, None for single-cluster — the slot that
        # makes it impossible for a K-tenant Compiled to be handed a
        # single cluster's arrays or vice versa);
        # ("preempt", dims, burst) → Compiled for the preemption burst
        self.compiled: dict = {}
        # bumped by invalidate(): a background compile that STARTED before a
        # backend loss must not register its executable afterward — it may
        # be bound to the dead runtime, and calling it would re-poison the
        # freshly recovered backend (recovery flap)
        self._epoch = 0
        # dispatch supervisor (sched/supervisor.py): background compile
        # failures are reported there to be counted and narrated
        self.supervisor = None
        # stored executables the dispatch layer actually ran (`call`), and
        # the ones it had to drop for the jit path on aval/pytree drift
        self.hits = 0
        self.type_error_drops = 0

    @staticmethod
    def _mesh_sig(mesh):
        from ..parallel.mesh import mesh_key

        return mesh_key(mesh)

    @classmethod
    def _cycle_key(cls, d: Dims, engine: str, extras: tuple, gang: bool,
                   fleet, mesh):
        # has_node_name is a per-batch routing fact the ENGINE slot already
        # carries (sched/cycle.py plan_engine), not a capacity
        return (replace(d, has_node_name=False), engine, extras, gang,
                fleet, cls._mesh_sig(mesh))

    def observe(self, d: Dims, n_nodes: int, n_existing: int,
                engine: str = "waves", extras: tuple = (),
                gang: bool = False, mesh=None, fleet=None) -> None:
        """Call once per cycle with live occupancy (and whether batches are
        gang-bearing — gangs trace a different program; and which mesh the
        cycle dispatches on — a sharded program is a different executable).
        Cheap when nothing is near a boundary. Warms one target per call;
        multiple crossing axes warm on successive cycles (single-axis
        targets first — the common case is one axis crossing at a time —
        then the joint one)."""
        if not self.enabled:
            return
        live = {"N": n_nodes, "E": n_existing}
        crossing = [ax for ax in _GROWTH_AXES
                    if getattr(d, ax) >= self.min_axis
                    and live[ax] >= self.threshold * getattr(d, ax)]
        if not crossing:
            return
        targets = [d.grown_for(**{ax: getattr(d, ax) + 1}) for ax in crossing]
        if len(crossing) > 1:
            targets.append(d.grown_for(
                **{ax: getattr(d, ax) + 1 for ax in crossing}))
        for target in targets:
            if target == d:
                continue
            key = self._cycle_key(target, engine, extras, gang, fleet, mesh)
            with self._mu:
                if key in self._warmed:
                    continue
                if self._inflight is not None and self._inflight.is_alive():
                    return  # one compile at a time; retry next cycle
                self._warmed.add(key)
                t = threading.Thread(
                    target=self._compile_fn,
                    args=(target, engine, extras, gang, mesh, fleet),
                    name=f"ktpu-prewarm-{target.N}x{target.E}", daemon=True)
                # start BEFORE publishing: wait() joins _inflight without
                # the lock, and joining a not-yet-started thread raises
                t.start()
                self._inflight = t
            return

    def _compile(self, d: Dims, engine: str, extras: tuple,
                 gang: bool, mesh=None, fleet=None) -> None:
        key = self._cycle_key(d, engine, extras, gang, fleet, mesh)
        epoch = self._epoch
        try:
            from ..utils import faultline
            from ..utils.faultline import InjectedDeviceError

            if faultline.should("device.error", "prewarm"):
                raise InjectedDeviceError(
                    "injected XlaRuntimeError at prewarm")
            with xla_scope("prewarm", key, _CYCLE_KEY_NAMES, on_path=False):
                compiled = self._lower_cycle(d, engine, extras, gang, mesh,
                                             fleet).compile()
            with self._mu:
                if epoch != self._epoch:
                    # invalidate() ran mid-compile (backend loss): this
                    # executable may be bound to the dead runtime — drop it
                    # and let a post-recovery warm redo the work
                    self._warmed.discard(key)
                    return
                self.compiled[key] = compiled
            self.warm_log.append((d, engine))
        except Exception as e:
            # prewarming is an optimization: a failed background compile
            # must never take down the scheduling loop; the live path will
            # compile on demand exactly as without a prewarmer. The
            # supervisor counts it.
            with self._mu:
                self._warmed.discard(key)
            if self.supervisor is not None:
                self.supervisor.note_compile_failure(e)

    @staticmethod
    def _lower_cycle(d: Dims, engine: str, extras: tuple, gang: bool,
                     mesh, fleet):
        """The cycle program at this signature, lowered from abstract
        shapes alone."""
        from .cycle import _schedule_batch_impl

        if fleet is not None:
            # a tenant-stack program (fleet/cycle.py): K virtual clusters
            # per dispatch — a structurally different executable from the
            # single-cluster one at the same dims
            from ..fleet.cycle import _fleet_cycle_impl
            from ..fleet.tables import abstract_fleet_args

            (tables, pending, keys, existing, quota,
             hw, ecfg) = abstract_fleet_args(d, int(fleet), mesh=mesh)
            return _fleet_cycle_impl.lower(
                tables, pending, keys, d.D, existing, engine, quota,
                hw, ecfg)
        (tables, pending, keys, existing, hw, ecfg,
         gang_args) = abstract_cycle_args(d, gang=gang, mesh=mesh)
        return _schedule_batch_impl.lower(
            tables, pending, keys, d.D, existing, engine, hw, ecfg,
            extras, tuple(1.0 for _ in extras), gang_args)

    def lookup(self, d: Dims, engine: str, extras: tuple, gang: bool,
               mesh=None, fleet=None):
        """The stored Compiled for this cycle signature, or None. Called on
        the dispatch hot path — one dict probe. The mesh signature is part
        of the key, so a single-device caller can NEVER receive a
        mesh-sharded executable (or vice versa) — the isolation that keeps
        a degraded wave from resharding its arrays onto lost devices. The
        fleet slot isolates the same way one layer up: a K-tenant stacked
        program and a single-cluster program at identical dims are
        different executables (fleet/cycle.py)."""
        return self.compiled.get(
            self._cycle_key(d, engine, extras, gang, fleet, mesh))

    def call(self, compiled, *args):
        """Run a stored executable: (True, result), or (False, None) when
        its avals/pytree no longer match the live arguments (TypeError) and
        the caller must take the ordinary jit path. Both outcomes are
        counted, so a run can say whether prewarmed executables were
        actually used or silently dropped."""
        try:
            out = compiled(*args)
        except TypeError:
            self.type_error_drops += 1
            return False, None
        self.hits += 1
        return True, out

    def invalidate(self) -> None:
        """Drop every stored executable and warm record, and fence out
        in-flight compiles (epoch bump: one that started before the loss
        must not register afterward). Called on backend loss
        (sched/supervisor.py): a Compiled bound to a dead runtime would
        raise mid-wave exactly when the system is trying to degrade."""
        with self._mu:
            self._epoch += 1
            self.compiled.clear()
            self._warmed.clear()

    def rewarm(self, d: Dims, engine: str = "waves", extras: tuple = (),
               gang: bool = False, mesh=None, fleet=None) -> bool:
        """Force a background compile of the CURRENT dims regardless of
        occupancy thresholds — the backend re-admission path: the recovered
        device's first wave should deserialize a warm executable, not pay a
        cold compile on the hot path. `mesh` is the mesh the NEXT wave will
        dispatch on (the supervisor passes the post-reform mesh, which may
        be narrower than the lost one — never the dead signature). If a
        compile is already in flight the rewarm CHAINS behind it (one
        compile at a time still holds) rather than being dropped. Returns
        True when the compile ran or was scheduled."""
        if not self.enabled:
            return False
        if max(d.N, d.E) < self.min_axis:
            return False  # small shapes recompile in seconds on demand
        key = self._cycle_key(d, engine, extras, gang, fleet, mesh)
        with self._mu:
            self._warmed.add(key)
            prev = self._inflight
            if prev is not None and prev.is_alive():
                def chained():
                    prev.join()
                    self._compile_fn(d, engine, extras, gang, mesh, fleet)

                t = threading.Thread(
                    target=chained,
                    name=f"ktpu-rewarm-{d.N}x{d.E}", daemon=True)
            else:
                t = threading.Thread(
                    target=self._compile_fn,
                    args=(d, engine, extras, gang, mesh, fleet),
                    name=f"ktpu-rewarm-{d.N}x{d.E}", daemon=True)
            # start BEFORE publishing (wait() joins without the lock; a
            # not-yet-started thread would raise there). rewarm runs on the
            # PROBER thread, so this race is cross-thread and real.
            t.start()
            self._inflight = t
        return True

    def ensure_warm(self, d: Dims, engine: str = "waves", extras: tuple = (),
                    gang: bool = False, mesh=None, fleet=None) -> bool:
        """The warm-standby beat (Scheduler.warm_standby): compile this
        exact signature in the background IF it is neither compiled nor
        already compiling — idempotent, unlike rewarm (which always
        respawns; it is the re-admission path where the old executable is
        known-poisoned). Returns True when a compile was scheduled."""
        if not self.enabled or max(d.N, d.E) < self.min_axis:
            return False
        key = self._cycle_key(d, engine, extras, gang, fleet, mesh)
        with self._mu:
            # _warmed covers both finished compiles (the key stays) and
            # in-flight ones (added before the thread starts)
            if key in self._warmed:
                return False
        return self.rewarm(d, engine, extras, gang, mesh, fleet)

    def ensure_patch_ladder(self, cache, snap, mesh=None) -> bool:
        """Background compile-ahead for the resident patch-scatter ladder
        (state/cache.py warm_patch_ladder): the per-bucket `_patch_rows`
        specializations the incremental snapshot path dispatches. Bulk
        waves amortize a first-seen rung's compile across thousands of
        pods; a streaming micro-wave (ISSUE 18) cannot — a 3-pod
        admission stalling ~0.5 s on a fresh rung IS the p99. Keyed by
        plane shapes, so a capacity growth re-warms the new ladder.
        Returns True when a compile pass was scheduled."""
        if not self.enabled or snap is None \
                or max(snap.dims.N, snap.dims.E) < self.min_axis:
            return False
        key = ("patch-ladder", snap.dims.N, snap.dims.E, snap.dims.P,
               self._mesh_sig(mesh))
        with self._mu:
            if key in self._warmed:
                return False
            if self._inflight is not None and self._inflight.is_alive():
                return False  # one compile at a time; retry next cycle
            self._warmed.add(key)

            def _run():
                try:
                    cache.warm_patch_ladder(snap, mesh=mesh)
                except Exception as e:  # noqa: BLE001 - warm is an
                    # optimization (see _compile)
                    with self._mu:
                        self._warmed.discard(key)
                    if self.supervisor is not None:
                        self.supervisor.note_compile_failure(e)

            t = threading.Thread(target=_run, daemon=True,
                                 name=f"ktpu-prewarm-ladder-{snap.dims.N}"
                                      f"x{snap.dims.E}")
            t.start()
            self._inflight = t
        return True

    # ---- preemption-burst program (sched/preemption.py _preempt) ---- #

    @classmethod
    def _preempt_key(cls, d: Dims, burst: int, mesh=None):
        # the burst program never sees the pending arrays, so P (and the
        # per-batch has_node_name flag) must not split the key: the warm
        # happens against the WAVE snapshot's dims while the lookup uses
        # the preemption pass's fresh snapshot — any P drift between the
        # two would orphan the prewarmed executable exactly when a storm
        # needs it
        return ("preempt", replace(d, has_node_name=False, P=1), burst,
                cls._mesh_sig(mesh))

    def observe_preempt(self, d: Dims, burst: int, mesh=None) -> None:
        """Warm the preemption-burst program for the CURRENT dims in the
        background. Unlike the cycle program (compiled by the first wave),
        nothing compiles the preempt what-if until the first preemption
        storm — which is exactly when a multi-second compile stall hurts
        most. The scheduler calls this once per steady cycle; each
        (dims, burst, mesh) signature compiles at most once."""
        if not self.enabled:
            return
        if max(d.N, d.E) < self.min_axis:
            return
        key = self._preempt_key(d, burst, mesh)
        with self._mu:
            if key in self._warmed:
                return
            if self._inflight_preempt is not None \
                    and self._inflight_preempt.is_alive():
                return  # one preempt compile at a time; retry next cycle
            self._warmed.add(key)
            t = threading.Thread(
                target=self._compile_preempt, args=(d, burst, mesh),
                name=f"ktpu-prewarm-preempt-{d.N}x{d.E}", daemon=True)
            t.start()  # before publishing: see observe()
            self._inflight_preempt = t

    def _compile_preempt(self, d: Dims, burst: int, mesh=None) -> None:
        key = self._preempt_key(d, burst, mesh)
        epoch = self._epoch
        try:
            from ..utils import faultline
            from ..utils.faultline import InjectedDeviceError
            from .preemption import _preempt

            if faultline.should("device.error", "prewarm"):
                raise InjectedDeviceError(
                    "injected XlaRuntimeError at prewarm")
            with xla_scope("prewarm", key, _PREEMPT_KEY_NAMES, on_path=False):
                (tables, existing, cls, nnr, prio, keys, pdb,
                 hw, ecfg) = abstract_preempt_args(d, burst, mesh=mesh)
                compiled = _preempt.lower(
                    tables, existing, cls, nnr, prio, d.D, keys, pdb, hw,
                    ecfg).compile()
            with self._mu:
                if epoch != self._epoch:
                    self._warmed.discard(key)  # invalidated mid-compile
                    return
                self.compiled[key] = compiled
            self.warm_log.append((d, "preempt"))
        except Exception as e:
            # same contract as _compile: never takes down the loop
            with self._mu:
                self._warmed.discard(key)
            if self.supervisor is not None:
                self.supervisor.note_compile_failure(e)

    def lookup_preempt(self, d: Dims, burst: int, mesh=None):
        return self.compiled.get(self._preempt_key(d, burst, mesh))

    def wait(self, timeout: Optional[float] = None) -> None:
        """Test/shutdown helper: join the in-flight compiles."""
        with self._mu:
            threads = (self._inflight, self._inflight_preempt)
        for t in threads:
            if t is not None:
                t.join(timeout)
