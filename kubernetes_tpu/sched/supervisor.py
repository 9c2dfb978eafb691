"""Dispatch supervisor: deadline watchdog, CPU degradation, TPU re-admission.

Every XLA call the scheduler makes — the wave dispatch (sched/cycle.py), the
preemption burst (sched/preemption.py), the extender score matrix, the
prewarmer's background compiles — runs under this supervisor. The failure
model: the device runtime can HANG mid-dispatch (a wedged execution does not
fail, it stalls forever), die with a ``JaxRuntimeError`` (OOM, worker crash,
backend loss), or come up so slowly it might as well be down. None of those
may cost the cluster a pod.

Mechanics:

  * ``submit(kind, shape_key, fn, fallback)`` runs ``fn`` (dispatch + blocking
    readback) on a watchdog worker thread and returns a handle; the caller
    overlaps host work and calls ``handle.result()``, which enforces a
    per-shape deadline. The deadline is budgeted per (kind, shape) — the first
    call at a shape gets the cold budget (it pays the XLA compile), later
    calls get ``mult × best-observed`` clamped to a floor, so a genuine hang
    at a warm shape is detected in seconds, not minutes.
  * On timeout / device error the backend is marked unhealthy and the SAME
    encoded arrays are re-dispatched on the CPU fallback backend
    (``jax.device_put`` onto the fallback device — the host staging mirrors
    in state/cache.py are the ground truth the arrays derive from, so the
    transfer is the cheap direction). While unhealthy, every subsequent call
    skips the primary entirely and dispatches on the fallback.
  * A genuinely hung worker thread cannot be cancelled from Python — it is
    abandoned (daemon thread, result discarded via the handle's abandoned
    flag) exactly as production TPU runtimes abandon wedged executions.
  * A background prober re-admits the primary with exponential backoff: one
    tiny dispatch per probe. On re-admission the prewarmer is invalidated
    (executables compiled against the lost backend may be dead) and re-warmed
    for the last-seen cycle signature in the background, so the first
    post-recovery wave pays a cache load, never a cold compile on the hot
    path.

Crash consistency is split with the scheduler: the supervisor guarantees a
wave either returns placements or raises ``DispatchAbandonedError`` with NO
partial effects (assumes happen only after readback, in the commit loop), and
``Scheduler.schedule_pending`` requeues the whole popped batch on abandonment
— forgetting cleanly instead of double-binding or losing pods.

Chaos seams (utils/faultline.py): ``device.hang`` / ``device.error`` /
``device.oom`` fire per supervised kind (sites ``cycle``, ``preempt``,
``scores``, ``prewarm``, ``probe``), ``device.fallback`` fails the fallback
path for total-loss drills.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from jax.errors import JaxRuntimeError

from ..utils import faultline
from ..utils.faultline import InjectedDeviceError
from .telemetry import xla_scope

#: exception classes that indicate the BACKEND failed (vs a bug in the
#: dispatched function, which must propagate to the caller unchanged)
DEVICE_ERRORS: Tuple[type, ...] = (JaxRuntimeError, InjectedDeviceError)


#: the parts of the `shape_key` each kind of supervised call is given, by
#: (kind, parts): what the XLA account names a signature's fields by
#: (sched/telemetry.py `XlaAccount.scope`; positions where none fits)
_SIG_NAMES: Dict[Tuple[str, int], Tuple[str, ...]] = {
    ("cycle", 5): ("dims", "engine", "extras", "gang", "mesh"),
    ("cycle", 4): ("dims", "engine", "fleet", "mesh"),
    ("scores", 3): ("dims", "extras", "mesh"),
    ("preempt", 3): ("dims", "burst", "mesh"),
}


class DispatchAbandonedError(RuntimeError):
    """Both the primary dispatch and the CPU fallback failed (or no fallback
    exists). The wave produced NO results and had NO side effects — the
    caller must requeue its inputs."""


class WatchdogTimeout(RuntimeError):
    """Internal marker: the primary dispatch exceeded its deadline."""


@dataclass
class SupervisorStats:
    """Operational counters, exported to bench (chaos stage) and tests."""

    watchdog_timeouts: int = 0
    device_errors: int = 0
    fallback_dispatches: int = 0
    degraded_cycles: int = 0          # cycle-kind dispatches served by fallback
    abandoned: int = 0                # both paths failed
    compile_failures: int = 0         # background (prewarm) compiles refused
    probes: int = 0
    recoveries: int = 0
    rewarms: int = 0
    last_recovery_s: Optional[float] = None
    unhealthy_since: Optional[float] = None
    last_failure: str = ""
    # wall seconds of fallback cycle dispatches — the degraded-mode latency
    # distribution (bench reports its max/p99 against the watchdog budget)
    degraded_cycle_seconds: List[float] = field(default_factory=list)


class _Handle:
    """One supervised dispatch in flight."""

    __slots__ = ("kind", "shape_key", "fallback", "deadline", "_done",
                 "_abandoned", "_result", "_error", "_t0", "_t_done", "sup",
                 "_primary_skipped")

    def __init__(self, sup: "DispatchSupervisor", kind: str, shape_key,
                 fallback, deadline: float):
        self.sup = sup
        self.kind = kind
        self.shape_key = shape_key
        self.fallback = fallback
        self.deadline = deadline
        self._done = threading.Event()
        # set when the watchdog gives up on the worker: a simulated hang
        # parks on this so the zombie exits promptly after abandonment
        self._abandoned = threading.Event()
        self._result: Any = None
        self._error: Optional[BaseException] = None
        self._t0 = time.perf_counter()
        self._t_done: Optional[float] = None
        self._primary_skipped = False

    # -- worker side -- #

    def _set_result(self, value: Any) -> None:
        self._t_done = time.perf_counter()
        self._result = value
        self._done.set()

    def _set_error(self, err: BaseException) -> None:
        self._t_done = time.perf_counter()
        self._error = err
        self._done.set()

    # -- caller side -- #

    def result(self) -> Any:
        return self.sup._resolve(self)


class DispatchSupervisor:
    """Per-scheduler supervisor. Creates NO threads until a dispatch is
    submitted; the prober thread exists only while the backend is unhealthy."""

    def __init__(self, prewarmer=None,
                 clock: Callable[[], float] = time.monotonic,
                 mesh_state=None):
        self.prewarmer = prewarmer
        self.clock = clock
        # parallel/mesh.py MeshState when the scheduler serves on a device
        # mesh: losing ANY device of the mesh is a whole-mesh loss (GSPMD
        # collectives span every chip), so unhealthy ⇒ the mesh is dropped
        # and degraded waves run single-device on the CPU fallback;
        # re-admission reforms the mesh — narrower unless a full-width
        # probe passes — and the next snapshot re-shards from host staging
        self.mesh_state = mesh_state
        # alternative mesh source for re-admission rewarm when there is no
        # node-axis mesh_state — the fleet server sets this to its
        # tenant-axis mesh so the rewarmed executable lands under the SAME
        # key the live fleet dispatch looks up (fleet/cycle.py)
        self.mesh_provider: Optional[Callable[[], Any]] = None
        # flight-recorder event sink (sched/telemetry.py
        # SchedulerTelemetry.note_supervisor_event): every health
        # transition / fallback / abandonment is narrated to the wave
        # record in flight, so a degraded tick is explainable from the
        # dump artifact. Called from the serving loop AND worker threads;
        # a raising sink must never take the ladder down.
        self.event_sink: Optional[Callable[[str, str], None]] = None
        # the `seq` the record of the wave in flight will get (the
        # telemetry's `recorder.next_seq`), for the XLA account's entries
        self.wave_seq: Optional[Callable[[], int]] = None
        self.stats = SupervisorStats()
        self._mu = threading.Lock()
        self._healthy = True
        # (kind, shape_key) → best observed successful primary duration.
        # Presence alone means "warm" (the compile already happened); the
        # min converges to the true warm dispatch time within ~2 calls.
        self._budgets: Dict[Tuple[str, Any], float] = {}
        self._prober: Optional[threading.Thread] = None
        # the current probe-dispatch worker: a probe against a hung runtime
        # wedges forever, so each probe gets its own deadline and a wedged
        # one is left behind (NOT re-spawned — one zombie max, and its
        # liveness doubles as "the backend is still hung")
        self._probe_worker: Optional[threading.Thread] = None
        self._primary_device = None
        self._fallback_device = None
        self._fallback_probed = False
        # last cycle signature (dims, engine, extras, gang) — what re-warms
        # on re-admission so recovery never eats a cold compile on-path
        self._cycle_sig: Optional[Tuple] = None

    # ------------------------------------------------------------------ #
    # deadline budgets
    # ------------------------------------------------------------------ #

    def deadline_for(self, kind: str, shape_key) -> float:
        rec = self._budgets.get((kind, shape_key))
        if rec is None:
            # cold: the call pays trace+compile — minutes at big shapes
            return float(os.environ.get("KTPU_DISPATCH_COLD_DEADLINE", "900"))
        env = os.environ.get("KTPU_DISPATCH_DEADLINE")
        if env:
            return float(env)
        mult = float(os.environ.get("KTPU_DISPATCH_DEADLINE_MULT", "8"))
        floor = float(os.environ.get("KTPU_DISPATCH_DEADLINE_FLOOR", "10"))
        return max(floor, mult * rec)

    def _record_success(self, kind: str, shape_key, duration: float) -> None:
        with self._mu:
            key = (kind, shape_key)
            prev = self._budgets.get(key)
            self._budgets[key] = duration if prev is None \
                else min(prev, duration)

    # ------------------------------------------------------------------ #
    # health
    # ------------------------------------------------------------------ #

    @property
    def healthy(self) -> bool:
        return self._healthy

    def snapshot_device(self):
        """Explicit placement for cache snapshots: None while healthy (the
        default device), the CPU fallback while degraded — so degraded-mode
        waves are encoded ONTO the fallback from host staging and never
        read from or write to the lost backend's buffers."""
        if self._healthy:
            return None
        return self._fallback_dev()

    def snapshot_mesh(self):
        """Mesh placement for cache snapshots: the live mesh while healthy,
        None while degraded (degraded waves are single-device on the CPU
        fallback — a collective over a mesh containing a dead chip would
        hang every healthy one too)."""
        if not self._healthy or self.mesh_state is None:
            return None
        return self.mesh_state.mesh

    def note_cycle_signature(self, dims, engine: str, extras: tuple,
                             gang: bool, fleet=None) -> None:
        """Remember what the live cycle program looks like so re-admission
        can warm exactly it (the mesh itself is NOT part of the note: the
        rewarm targets whatever mesh exists post-reform, never the dead
        one's signature). `fleet` is the tenant-stack count when the live
        program is a fleet cycle (fleet/cycle.py) — the rewarm must target
        the stacked executable, not the single-cluster one."""
        self._cycle_sig = (dims, engine, extras, gang, fleet)

    def _emit(self, kind: str, detail: str = "") -> None:
        sink = self.event_sink
        if sink is None:
            return
        try:
            sink(kind, detail)
        except Exception:  # noqa: BLE001 - telemetry never breaks dispatch
            pass

    def _mark_unhealthy(self, reason: str) -> None:
        self._emit("degraded", reason)
        with self._mu:
            self.stats.last_failure = reason
            if not self._healthy:
                return
            self._healthy = False
            self.stats.unhealthy_since = self.clock()
            # a mesh containing the lost device is wholly untrusted: drop
            # it NOW so snapshot_mesh() routes degraded waves single-device
            if self.mesh_state is not None:
                try:
                    self.mesh_state.on_backend_loss()
                except Exception:  # noqa: BLE001 - health flip must not die
                    pass
            # executables compiled against the lost backend may be dead —
            # drop them; the rewarm on re-admission repopulates
            if self.prewarmer is not None:
                try:
                    self.prewarmer.invalidate()
                except Exception:  # noqa: BLE001 - health flip must not die
                    pass
            t = threading.Thread(target=self._probe_loop,
                                 name="ktpu-backend-prober", daemon=True)
            self._prober = t
            t.start()

    def _probe_loop(self) -> None:
        """Re-admit the primary backend with exponential backoff."""
        backoff = float(os.environ.get("KTPU_PROBE_BACKOFF", "0.25"))
        cap = float(os.environ.get("KTPU_PROBE_BACKOFF_CAP", "30"))
        while not self._healthy:
            time.sleep(backoff)
            self.stats.probes += 1
            if self._probe_once():
                self._readmit()
                return
            backoff = min(backoff * 2, cap)

    def _probe_once(self) -> bool:
        if faultline.should("device.hang", "probe") or \
                faultline.should("device.error", "probe"):
            return False
        prev = self._probe_worker
        if prev is not None and prev.is_alive():
            # the last probe dispatch is still wedged inside the runtime:
            # that IS the answer (still hung), and spawning another worker
            # per backoff round would leak a thread each — wait it out
            return False
        done = threading.Event()
        ok = [False]

        def probe() -> None:
            try:
                import jax
                import jax.numpy as jnp

                dev = self._primary_device or jax.devices()[0]
                x = jax.device_put(jnp.int32(1), dev)
                jax.block_until_ready(x + jnp.int32(1))
                ok[0] = True
            except Exception:  # noqa: BLE001 - probe failure = still down
                pass
            finally:
                done.set()

        t = threading.Thread(target=probe, name="ktpu-probe-dispatch",
                             daemon=True)
        self._probe_worker = t
        t.start()
        # a hung probe must not wedge the prober loop: bounded wait, the
        # worker is abandoned on timeout exactly like a hung dispatch
        done.wait(float(os.environ.get("KTPU_PROBE_DEADLINE", "10")))
        return ok[0]

    def _probe_mesh_full(self) -> bool:
        """Can the mesh come back at FULL width? One tiny collective over
        every device the full mesh would use — a chip that initializes but
        cannot join a psum must keep the mesh narrow. The `mesh.degrade`
        chaos seam forces the narrow path in drills. The collective runs on
        its own worker under the probe deadline — a chip that re-inits but
        WEDGES mid-collective must cost one abandoned thread, not a prober
        blocked forever (same contract as _probe_once)."""
        if faultline.should("mesh.degrade", "probe"):
            return False
        done = threading.Event()
        ok = [False]

        def probe() -> None:
            try:
                import jax
                import jax.numpy as jnp
                from jax.sharding import NamedSharding, PartitionSpec

                from ..parallel.mesh import NODE_AXIS, make_mesh

                want = self.mesh_state._requested or len(jax.devices())
                if want <= 1:
                    return
                m = make_mesh(1 << (max(want, 1).bit_length() - 1))
                n = len(m.devices.flat)
                x = jax.device_put(jnp.arange(n, dtype=jnp.int32),
                                   NamedSharding(m,
                                                 PartitionSpec(NODE_AXIS)))
                total = int(jax.jit(lambda a: a.sum())(x))
                ok[0] = total == n * (n - 1) // 2
            except Exception:  # noqa: BLE001 - probe failure = stay narrow
                pass
            finally:
                done.set()

        t = threading.Thread(target=probe, name="ktpu-mesh-full-probe",
                             daemon=True)
        t.start()
        done.wait(float(os.environ.get("KTPU_PROBE_DEADLINE", "10")))
        return ok[0]

    def _readmit(self) -> None:
        with self._mu:
            if self._healthy:
                return
            self._healthy = True
            self._emit("recovery", self.stats.last_failure)
            self.stats.recoveries += 1
            if self.stats.unhealthy_since is not None:
                self.stats.last_recovery_s = round(
                    self.clock() - self.stats.unhealthy_since, 3)
            self.stats.unhealthy_since = None
            sig = self._cycle_sig
        mesh = None
        if self.mesh_state is not None:
            # reform the mesh from the devices that are live NOW: full
            # width when a whole-mesh collective proves every chip answers,
            # else narrower (losing one device of an 8-way mesh serves on
            # 4). Either way the Mesh OBJECT is fresh, which forces
            # state/cache.py to re-shard resident state from host staging.
            try:
                mesh = self.mesh_state.reform(full=self._probe_mesh_full())
            except Exception:  # noqa: BLE001 - single-device serving is
                mesh = None    # always a legal landing spot
        elif self.mesh_provider is not None:
            try:
                mesh = self.mesh_provider()
            except Exception:  # noqa: BLE001 - rewarm is an optimization
                mesh = None
        if self.prewarmer is not None and sig is not None:
            dims, engine, extras, gang, fleet = sig
            try:
                if self.prewarmer.rewarm(dims, engine=engine, extras=extras,
                                         gang=gang, mesh=mesh, fleet=fleet):
                    self.stats.rewarms += 1
                    self._emit("rewarm", engine)
            except Exception:  # noqa: BLE001 - rewarm is an optimization
                pass

    def note_compile_failure(self, exc: BaseException) -> None:
        """Called by the prewarmer's background compile thread. Counted and
        narrated, never a health transition: lower+compile executes nothing
        on the device, so a ``JaxRuntimeError`` here is the compiler refusing
        THAT program (RESOURCE_EXHAUSTED for a next-bucket shape nobody
        serves yet) — deterministic, and no evidence about the backend the
        live waves run on. Degrading for it would move serving to the CPU
        under a healthy chip, and the prober's tiny dispatch would re-admit
        at once only for the next cycle's retry to fail again. A backend that
        IS gone fails the next live dispatch, where the ladder applies."""
        with self._mu:
            self.stats.compile_failures += 1
            self.stats.last_failure = f"prewarm compile: {exc!r}"
        self._emit("compile_failure", repr(exc))

    # ------------------------------------------------------------------ #
    # dispatch
    # ------------------------------------------------------------------ #

    def submit(self, kind: str, shape_key, fn: Callable[[], Any],
               fallback: Optional[Callable[[Any], Any]] = None) -> _Handle:
        """Start ``fn`` (dispatch + blocking readback) on a watchdog worker.
        Returns a handle; ``handle.result()`` enforces the deadline and runs
        the degradation ladder. While unhealthy the primary is skipped
        entirely and ``result()`` dispatches the fallback inline.

        ``fallback(device, hung)`` re-runs the work on the fallback device;
        ``hung=True`` means the primary's buffers are untouchable (a
        transfer from a wedged runtime blocks forever) — rebuild inputs
        from host state instead of reading them back."""
        deadline = self.deadline_for(kind, shape_key)
        h = _Handle(self, kind, shape_key, fallback, deadline)
        if not self._healthy:
            h._primary_skipped = True
            return h
        # whatever `fn` compiles is the XLA account's under this call's
        # kind and key, entered ON the worker (jax reports a compile on the
        # thread that runs it), with the supervisor's own verdict: no
        # budget for the key yet is a cold call
        scope = self._xla_scope(h, cold=(kind, shape_key) not in self._budgets)
        if self._primary_device is None:
            try:
                import jax

                self._primary_device = jax.devices()[0]
            except Exception:  # noqa: BLE001 - resolved lazily again later
                pass

        def work() -> None:
            try:
                if faultline.should("device.hang", kind):
                    # simulated mid-dispatch hang: park until the watchdog
                    # abandons us (plus a margin), then exit quietly
                    h._abandoned.wait(deadline + 30.0)
                    raise InjectedDeviceError(
                        f"injected device hang at {kind}")
                if faultline.should("device.error", kind):
                    raise InjectedDeviceError(
                        f"injected XlaRuntimeError at {kind}")
                if faultline.should("device.oom", kind):
                    raise InjectedDeviceError(
                        f"RESOURCE_EXHAUSTED: injected device OOM at {kind}")
                with scope:
                    h._set_result(fn())
            except BaseException as e:  # noqa: BLE001 - ferried to caller
                h._set_error(e)

        threading.Thread(target=work, name=f"ktpu-dispatch-{kind}",
                         daemon=True).start()
        return h

    def _xla_scope(self, h: _Handle, cold: Optional[bool] = None,
                   stage: Optional[str] = None):
        key = h.shape_key
        names = _SIG_NAMES.get((h.kind, len(key)), ()) \
            if isinstance(key, tuple) else ()
        return xla_scope(stage or h.kind, key, names, on_path=True, cold=cold,
                         seq=self.wave_seq() if self.wave_seq else None,
                         sink=self._emit)

    def run(self, kind: str, shape_key, fn: Callable[[], Any],
            fallback: Optional[Callable[[Any], Any]] = None) -> Any:
        """Blocking convenience: submit + result."""
        return self.submit(kind, shape_key, fn, fallback).result()

    def _resolve(self, h: _Handle) -> Any:
        if h._primary_skipped:
            return self._run_fallback(h, reason="backend unhealthy")
        # the deadline counts from DISPATCH start, not from result():
        # the caller deliberately overlaps host work between submit and
        # result, and that overlap must neither extend a hung dispatch's
        # detection time nor leak into the recorded warm-dispatch budget
        remaining = h.deadline - (time.perf_counter() - h._t0)
        if not h._done.wait(max(remaining, 0.001)):
            # the worker is wedged: abandon it (it is a daemon thread; a
            # REAL hang leaks it, exactly like abandoning a wedged XLA
            # execution), mark the backend lost, degrade
            h._abandoned.set()
            self.stats.watchdog_timeouts += 1
            self._emit("watchdog_timeout",
                       f"{h.kind} exceeded {h.deadline:.3g}s")
            self._mark_unhealthy(
                f"{h.kind} dispatch exceeded {h.deadline:.3g}s deadline")
            return self._run_fallback(
                h, reason=f"watchdog timeout after {h.deadline:.3g}s",
                hung=True)
        if h._error is not None:
            if isinstance(h._error, DEVICE_ERRORS):
                self.stats.device_errors += 1
                self._mark_unhealthy(f"{h.kind}: {h._error!r}")
                return self._run_fallback(h, reason=repr(h._error))
            raise h._error  # a bug in fn, not a backend failure
        self._record_success(h.kind, h.shape_key,
                             (h._t_done or time.perf_counter()) - h._t0)
        return h._result

    def _fallback_dev(self):
        if not self._fallback_probed:
            self._fallback_probed = True
            try:
                import jax

                self._fallback_device = jax.devices("cpu")[0]
            except Exception:  # noqa: BLE001 - no CPU backend available
                self._fallback_device = None
        return self._fallback_device

    def _run_fallback(self, h: _Handle, reason: str,
                      hung: bool = False) -> Any:
        dev = self._fallback_dev()
        if h.fallback is None or dev is None:
            self.stats.abandoned += 1
            self._emit("abandoned", f"{h.kind}: no fallback ({reason})")
            raise DispatchAbandonedError(
                f"{h.kind} dispatch abandoned ({reason}); no fallback "
                f"available")
        t0 = time.perf_counter()
        try:
            if faultline.should("device.fallback", h.kind):
                raise InjectedDeviceError(
                    f"injected fallback failure at {h.kind}")
            # hung=True tells the fallback the primary's buffers are
            # untouchable (a transfer from a wedged runtime blocks forever
            # with no watchdog): rebuild from host state instead
            with self._xla_scope(h, stage=f"{h.kind}/fallback"):
                out = h.fallback(dev, hung)
        except Exception as e:  # noqa: BLE001 - the ladder ends here
            self.stats.abandoned += 1
            self._emit("abandoned",
                       f"{h.kind}: primary ({reason}), fallback ({e!r})")
            raise DispatchAbandonedError(
                f"{h.kind} dispatch abandoned: primary failed ({reason}), "
                f"fallback failed ({e!r})") from e
        self.stats.fallback_dispatches += 1
        self._emit("fallback", f"{h.kind}: {reason}")
        if h.kind == "cycle":
            self.stats.degraded_cycles += 1
            if len(self.stats.degraded_cycle_seconds) < 1024:
                self.stats.degraded_cycle_seconds.append(
                    round(time.perf_counter() - t0, 4))
        return out

    # ------------------------------------------------------------------ #
    # lifecycle helpers (tests / shutdown)
    # ------------------------------------------------------------------ #

    def wait_recovered(self, timeout: float = 10.0) -> bool:
        """Block until the prober re-admits the primary (tests)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._healthy:
                return True
            time.sleep(0.02)
        return self._healthy
