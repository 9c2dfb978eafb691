"""The stateful, watch-driven scheduler: cache + queue + batched device cycle.

This is the analog of the reference's Scheduler struct and its wiring
(pkg/scheduler/scheduler.go:79-122, eventhandlers.go:335-441), with the
per-pod scheduleOne loop (scheduler.go:596-763) replaced by a per-*wave*
batched cycle: pop up to `batch_size` pods, one device dispatch schedules all
of them with sequential assume semantics (ops/assign.py lax.scan), then commit.

Event handlers mirror eventhandlers.go:
  * assigned-pod add/update/delete      → cache            (:360-362)
  * unassigned-pod add/update/delete    → queue            (:367-385, filtered
    by `responsible_for` — the schedulerName check, :277-282)
  * node add/update/delete              → cache + queue.move_all_to_active
                                                           (:392-396)
Failures feed the backoff/unschedulable queues exactly as FitError handling
does (scheduler.go:436-448); bind errors roll back via cache.forget_pod
(scheduler.go:717,732).
"""

from __future__ import annotations

import resource
import time
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Protocol, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..api.types import DEFAULT_SCHEDULER_NAME, Node, Pod
from ..component import trace
from ..parallel.mesh import mesh_key
from ..state.cache import SchedulerCache, Snapshot
from ..state.dims import Dims
from ..state.encode import Encoder
from .cycle import (UNSCHEDULABLE_TAINT_KEY, _schedule_batch,
                    micro_snapshot_with_keys, plan_engine,
                    snapshot_with_keys)
from .metrics import BINDING_DURATION, MICRO_WAVES
from .queue import PriorityQueue
from .supervisor import DispatchAbandonedError
from .telemetry import _NULL_SPAN, xla_scope


class Binder(Protocol):
    """The Binding write (scheduler.go:565 b.Client.CoreV1().Pods(...).Bind).
    Returns True on success; False/raise → rollback via ForgetPod.

    A binder whose write sleeps through a round trip may also have
    `window()`: the window a wave keeps several of its writes in flight
    through (sched/server.py `BindWindow`: `width`, `submit`, `gather`,
    `spans`), or None for one at a time; `bind` is then called from the
    window's threads. Without the method a wave awaits each `bind` in its
    turn, on its own thread."""

    def bind(self, pod: Pod, node_name: str) -> bool: ...


class RecordingBinder:
    """Test binder in the spirit of the fake clientset: records bindings and
    optionally fails selected pods."""

    def __init__(self, fail_keys: Sequence[str] = ()) -> None:
        self.bound: List[Tuple[str, str]] = []
        self.fail_keys = set(fail_keys)

    def bind(self, pod: Pod, node_name: str) -> bool:
        if pod.key in self.fail_keys:
            return False
        self.bound.append((pod.key, node_name))
        return True


@dataclass
class CycleStats:
    """Per-wave outcome; feeds the scheduling metrics
    (metrics/metrics.go:32-99)."""

    attempted: int = 0
    scheduled: int = 0
    unschedulable: int = 0
    bind_errors: int = 0
    # pods whose wave dispatch was abandoned (primary AND fallback failed):
    # requeued promptly with attempts preserved — not failures of the pods
    aborted: int = 0
    # fleet-tick telemetry (fleet/server.py, per TENANT per tick): pods
    # sent back to the queue without a failure verdict this tick (DRF
    # quota clamp, storm requeue, abort — they retry promptly, unlike
    # `unschedulable`), and whether this tenant's tick was degraded (its
    # injected watch storm forced a full re-encode + requeue). The chaos
    # suite and the fleet bench stage assert tenant ISOLATION from these
    # counters instead of scraping logs.
    requeued: int = 0
    degraded: int = 0
    # overload governor (sched/overload.py): pods parked in the deferred
    # lane this wave (SHED_LOW — deferred, never dropped), and whether the
    # wave was paused outright by the open commit breaker (no pop, no
    # device time)
    shed: int = 0
    commit_paused: int = 0
    # streaming micro-wave admission (ISSUE 18): 1 when this wave was a
    # micro-wave — a small fresh-delta batch grafted onto the resident
    # snapshot between bulk cycles (sub-cycle watch→bind latency)
    micro: int = 0
    # pods deferred by the DRF quota pre-mask this tick (fleet/server.py;
    # a subset of `requeued`) — routed through sched/metrics.py
    # observe_fleet_tick so the fleet bench asserts the clamp from the
    # tenant-labelled DRF_CLAMPED counter, not from server internals
    drf_clamped: int = 0
    cycle_seconds: float = 0.0
    # a wave whose Bindings went out through the binder's window
    # (`_commit_windowed`): the seconds of its writes, each its own, and
    # the wall seconds from the first hand-out to the last answer; their
    # ratio is the mean number in flight. Onto the record in `_record`.
    bind_request_s: float = 0.0
    bind_window_s: float = 0.0
    assignments: Dict[str, str] = field(default_factory=dict)
    # pod keys that failed this wave (feeds FailedScheduling events)
    failed_keys: List[str] = field(default_factory=list)
    # gang admission (ops/gang.py), on a gang-bearing wave only: the wave
    # fixpoints the dispatch ran, the pod groups in the batch, those it
    # refused, and for each member of a refused group why (the message of
    # its FailedScheduling Event, by pod key)
    gang_rounds: int = 0
    gang_groups: int = 0
    gang_groups_rejected: int = 0
    gang_refusals: Dict[str, str] = field(default_factory=dict)
    # volumes (volume/binder.py), on a wave that popped a pod with a volume
    # only: the pods whose claims resolved, and for each pod left waiting on
    # its claims why (its FailedScheduling Event's message, by pod key)
    volume_pods: int = 0
    volume_waits: Dict[str, str] = field(default_factory=dict)
    # pins (`PodArrays.pin`: a DaemonSet's pods), on a wave whose batch
    # holds one only: the pods with a pin, and those of them whose node
    # refused them
    pinned: int = 0
    pin_classes: int = 0
    pinned_unfit: int = 0
    # fill (ops/waves.py "Fill"), on a wave whose batch holds a filling
    # class only: the pods such classes placed; and extended resources, on
    # a wave whose batch holds a pod that asks one: per resource name the
    # pods that ask it, (decided onto a node, left unschedulable)
    fill_pods: int = 0
    extended_pods: Dict[str, Tuple[int, int]] = field(default_factory=dict)


@dataclass
class Wave:
    """What one wave's stages hand each other (`Scheduler._run_wave`): a
    stage reads what earlier stages set and sets its own fields."""

    now: float
    t0: float                       # perf_counter at the wave's start
    span: Any = _NULL_SPAN          # sched/telemetry.py wave span
    minor_faults0: int = 0          # _minor_faults() at the wave's start
    # ---- set by admit ---- #
    cycle: int = 0
    micro: bool = False
    batch: List[Tuple[Pod, int]] = field(default_factory=list)
    # the pods an extender claimed: per pod, after the batched wave
    ext_batch: List[Tuple[Pod, int]] = field(default_factory=list)
    stats: CycleStats = field(default_factory=CycleStats)
    extra: Dict[str, Any] = field(default_factory=dict)  # onto the record
    # ---- set by decide ---- #
    snap: Optional[Snapshot] = None
    keys: Any = None
    snap_mode: str = ""             # "full" | "patch" | "cached"
    engine: str = ""
    node_order: Sequence[str] = ()  # of the snapshot ACTUALLY dispatched
    node_idx: Any = None            # node row per batch pod, -1 = none
    attribution: Any = None         # the dispatch's ExplainResult, on host
    gang_verdict: Any = None        # ops/gang.py GangVerdict, on host
    rounds: Any = None              # the waves engine's rounds, on host
    fill: Any = None                # its AssignResult.fill, on host
    # ---- set by commit ---- #
    explain: Optional[Dict[str, Any]] = None   # the explainer's rendering

    @property
    def dims(self) -> Optional[Dims]:
        return self.snap.dims if self.snap is not None else None


def _minor_faults() -> int:
    """Page faults the process has taken so far that needed no I/O, over
    all its threads: the pump's and the informers' beside the wave's own."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


class Scheduler:
    """Single-profile scheduler. `schedule_pending` is the wave analog of
    scheduleOne; call it from a loop (or `run_until_idle`)."""

    def __init__(
        self,
        binder: Binder,
        cache: Optional[SchedulerCache] = None,
        queue: Optional[PriorityQueue] = None,
        scheduler_name: str = DEFAULT_SCHEDULER_NAME,
        batch_size: int = 4096,
        base_dims: Optional[Dims] = None,
        clock: Callable[[], float] = time.monotonic,
        preemptor: Optional["object"] = None,
        extenders: Sequence["object"] = (),
        framework: Optional["object"] = None,
        mesh: object = None,
        ledger: Optional["object"] = None,
        fence_source: Optional[Callable[[], int]] = None,
        microwave: Optional[bool] = None,
    ) -> None:
        self.binder = binder
        # exactly-once binding across crash/restart (sched/ledger.py): when
        # a BindIntentLedger is attached, every wave's placements are
        # durably recorded BEFORE the first Binding write and retired after
        # the last — a crash anywhere in between is recoverable via
        # `recover()`. None (the default) keeps the in-memory-only pipeline.
        self.ledger = ledger
        # fencing token source (LeaderElector.fencing_token): stamped into
        # every intent record; the API binder stamps it into Binding writes
        # so the apiserver can reject a deposed leader. None = token 0.
        self.fence_source = fence_source
        self.cache = cache or SchedulerCache()
        self.queue = queue or PriorityQueue()
        self.scheduler_name = scheduler_name
        self.batch_size = batch_size
        self.base_dims = base_dims
        self.clock = clock
        self.encoder = Encoder()
        self.preemptor = preemptor  # set by sched.preemption.attach()
        # HTTPExtender list (generic_scheduler.go:547-574,834-869). When any
        # extender is configured, pods it is interested in take the per-pod
        # path (`_schedule_one_with_extenders`) — the extender protocol is
        # per-pod HTTP anyway, so the reference's own round-trip cost applies.
        self.extenders = list(extenders)
        # Framework host lifecycle points (Reserve/Permit/PreBind/Bind/
        # PostBind/Unreserve) guard the commit path (scheduler.go:660-762).
        # The device-evaluated points run inside the fused cycle; None keeps
        # the plain fast path.
        self.framework = framework
        # hardPodAffinitySymmetricWeight (apis/config/types.go:70); set from
        # KubeSchedulerConfiguration by the server wiring
        self.hard_pod_affinity_weight = 1.0
        # fused-engine plugin composition (ops/lattice.py EngineConfig);
        # None = the default provider's set
        self.engine_config = None
        # configured score plugins outside the fused set reach the dispatch
        # as a static per-class bias (framework/plugins.py extra_score_plugins)
        from ..framework.plugins import extra_score_plugins

        extra_score = extra_score_plugins(framework)
        self._extras = tuple(p for p, _ in extra_score)
        self._extra_w = tuple(w for _, w in extra_score)
        # gang mechanism selection: the device gang engine (ops/gang.py)
        # owns pod groups UNLESS the Coscheduling Permit plugin is enabled —
        # then the host waiting-map path does (one mechanism per config;
        # both holding the same group would double-gate it). The plugin is
        # auto-wired here: releases complete through complete_waiting, and
        # quorum counts come from the cache's group accounting.
        self._device_gangs = True
        if framework is not None:
            for p in getattr(framework, "permit_plugins", ()):
                if getattr(p, "name", "") == "Coscheduling":
                    self._device_gangs = False
                    if getattr(p, "on_release", None) is None:
                        p.on_release = self.complete_waiting
                    if getattr(p, "bound_count", None) is None:
                        p.bound_count = self.cache.group_bound_count
        # key → (attempts, CycleState, node_name, original pod, binder_ext)
        self._waiting_meta: Dict[str, Tuple] = {}
        self.waiting_bind_errors = 0  # bind failures on the waiting-release path
        # compile-ahead on capacity growth (sched/prewarm.py): the next
        # Dims bucket compiles in the background BEFORE occupancy crosses
        # it, so bucket growth never stalls the scheduling loop
        from .prewarm import BucketPrewarmer

        self.prewarmer = BucketPrewarmer()
        # live mesh serving (parallel/mesh.py): `mesh` may be a MeshState,
        # a device count, or "auto" (all visible devices); None consults
        # KTPU_MESH (unset/0 = single-device serving, the pre-mesh
        # behavior). With a mesh, snapshots keep ClusterTables RESIDENT
        # sharded across it (node axis split) and the wave/preempt/score
        # programs compile under GSPMD sharding annotations.
        self.mesh_state = self._make_mesh_state(mesh)
        # every XLA call (wave dispatch, preemption burst, extender scores,
        # background compiles) runs under the dispatch supervisor: deadline
        # watchdog, CPU degradation on backend loss, prober re-admission,
        # mesh drop/reform across device loss (sched/supervisor.py)
        from .supervisor import DispatchSupervisor

        self.supervisor = DispatchSupervisor(prewarmer=self.prewarmer,
                                             mesh_state=self.mesh_state)
        self.prewarmer.supervisor = self.supervisor
        # observability (sched/telemetry.py, ISSUE 7): per-pod watch→bind
        # latency (stamps in THIS scheduler's clock domain via the queue's
        # tracker hook), per-wave phase spans + flight-recorder ring, and
        # the supervisor's event narration. KTPU_TELEMETRY=0 disables all
        # of it (the bench overhead baseline).
        from .telemetry import SchedulerTelemetry

        self.telemetry = SchedulerTelemetry(name=scheduler_name)
        if self.telemetry.enabled:
            self.queue.tracker = self.telemetry.tracker
        self.supervisor.event_sink = self.telemetry.note_supervisor_event
        self.supervisor.wave_seq = self.telemetry.recorder.next_seq
        # overload governor (sched/overload.py, ISSUE 9): brownout modes,
        # priority-aware shedding into the queue's deferred lane, adaptive
        # wave sizing, and the commit-path circuit breaker. None when
        # KTPU_OVERLOAD=0 — the kill switch keeps the wave pipeline
        # byte-for-byte the pre-governor code path.
        from .overload import build_governor

        self.governor = build_governor(
            batch_size, clock=self.clock,
            event_sink=self.telemetry.note_supervisor_event,
            name=scheduler_name)
        # decision provenance (sched/explain.py, ISSUE 10): when
        # KTPU_EXPLAIN is on (or the config's decisionProvenance flag —
        # enable_explain()), every wave's dispatch also runs the on-device
        # attribution reduction and this explainer renders it into events/
        # metrics/the flight-recorder record/the /debug/why surface. None
        # (the default) keeps the dispatch the byte-for-byte
        # pre-provenance program — the KTPU_OVERLOAD kill-switch
        # discipline.
        from .explain import build_explainer

        self.explainer = build_explainer(name=scheduler_name,
                                         clock=self.clock)
        # Events queued and not yet written (the server's broadcaster's
        # `pending`): read at every pop onto the wave's record. None = a
        # scheduler no server drives, which queues none.
        self.events_pending: Optional[Callable[[], int]] = None
        # the watch plane since the previous wave's end (the server's
        # `_watch_plane`): `informer_relists` of its informers and, where
        # the store is in this process, the pump's `pump_lag_max`, what it
        # cost (`pump_busy_s`, `pump_turns`, `pump_events`) and the
        # `watch_evictions`. Read at a wave's END onto its record.
        self.watch_plane: Optional[Callable[[], Dict[str, Any]]] = None
        # a pod's PersistentVolumeClaims are resolved against the server's
        # listers at the head of a wave's `snapshot` phase
        # (`_resolve_volumes`; volume/binder.py SchedulerVolumeBinder). None
        # (no server, or `volume_binding=False`): claims are not followed.
        self.volume_binder: Optional["object"] = None
        # keys of the pods the last resolution left waiting on their claims
        # (unschedulableQ; a PVC or PV event moves them back)
        self.volume_waiting: set = set()
        # streaming micro-waves (ISSUE 18): when the live backlog is
        # nothing but a handful of FRESH watch deltas, admit them through
        # a small fixed-capacity wave grafted onto the resident snapshot
        # (state/cache.py micro_graft) instead of parking them until a
        # bulk cycle pops. Opt-in: KTPU_MICROWAVE=1 (or the ctor flag);
        # off/unset keeps the wave pipeline byte-for-byte the bulk-only
        # code path — the micro branches below are simply never taken.
        import os as _os

        if microwave is None:
            microwave = _os.environ.get(
                "KTPU_MICROWAVE", "") not in ("", "0", "off")
        self.microwave = bool(microwave)
        # lane capacity: a fresh backlog deeper than this is bulk work
        # (one big wave beats many small ones); clamped to the configured
        # batch so tests with tiny batches keep their wave-size contract
        self.micro_max_batch = min(
            int(_os.environ.get("KTPU_MICRO_MAX_BATCH", "128")),
            max(int(batch_size), 1))
        # coalesce window: hold a not-yet-full lane this long so
        # near-simultaneous deltas share one dispatch. 0 (default) admits
        # immediately — latency-optimal; docs/PERF.md has the math for
        # when a window pays.
        self.micro_coalesce_s = float(
            _os.environ.get("KTPU_MICRO_COALESCE_S", "0"))
        # every micro wave encodes at ONE fixed pending capacity, so all
        # micro dispatches share a single compile signature per cluster
        # shape regardless of delta burstiness
        from ..state.dims import bucket as _bucket

        self._micro_p = _bucket(self.micro_max_batch)
        self.micro_waves = 0

    def enable_explain(self, sink=None) -> None:
        """Force decision provenance on for this scheduler (the
        KubeSchedulerConfiguration `decisionProvenance: true` path —
        per-process, no env)."""
        if self.explainer is None:
            from .explain import build_explainer

            self.explainer = build_explainer(
                name=self.scheduler_name, clock=self.clock, enabled=True,
                sink=sink)
        elif sink is not None and self.explainer.sink is None:
            self.explainer.sink = sink

    @staticmethod
    def _make_mesh_state(mesh):
        import os

        from ..parallel.mesh import MeshState

        if mesh is None:
            env = os.environ.get("KTPU_MESH", "")
            if not env or env in ("0", "off"):
                return None
            mesh = env
        if isinstance(mesh, MeshState):
            return mesh
        if isinstance(mesh, str):
            # bounds-checked: KTPU_MESH=garbage must degrade to single-
            # device serving, never crash Scheduler() at import-of-config
            # time (clamped 0 sentinel → no mesh, same as unset)
            from ..utils.envparse import clamped_int

            if mesh == "auto":
                return MeshState(None)
            n = clamped_int(mesh, 0, 0, 4096)
            return MeshState(n) if n > 1 else None
        if isinstance(mesh, int):
            return MeshState(mesh) if mesh > 1 else None
        # a raw jax.sharding.Mesh: adopt it as the live mesh
        ms = MeshState(len(mesh.devices.flat))
        ms.mesh = mesh
        return ms

    # ------------------------------------------------------------------ #
    # event handlers (eventhandlers.go)
    # ------------------------------------------------------------------ #

    def responsible_for(self, pod: Pod) -> bool:
        """responsibleForPod (eventhandlers.go:282)."""
        return pod.scheduler_name == self.scheduler_name

    def on_pod_add(self, pod: Pod, arrived: Optional[float] = None) -> None:
        """`arrived`: when the pod reached the caller, on this scheduler's
        clock, where the caller read that before it took its lock (the
        wait for a lock that a wave holds is part of the pod's wait); the
        queue's stamp and the first-seen stamp. Now, if not given."""
        if pod.node_name:                       # assignedPod (:277)
            if self.cache.is_assumed(pod.key) or self.cache.get_pod(pod.key) is None:
                self._confirm(pod)
            # a new pod landing may unblock anti-affinity waiters etc.
            self.queue.move_all_to_active(self.clock())
        elif self.responsible_for(pod):
            self.queue.add(pod, now=self.clock() if arrived is None
                           else arrived)

    def _confirm(self, pod: Pod) -> None:
        """`cache.add_pod` for a bound pod the informer delivers. Where it is
        the echo of this scheduler's own Binding and the pod names claims,
        what they attach is what the wave resolved and the node has counted
        since the assume: the claim lister may not have heard yet of a claim
        bound at placement, and the count must not dip meanwhile."""
        if pod.claims:
            assumed = self.cache.get_pod(pod.key)
            if assumed is not None:
                pod.volumes = assumed.volumes
        self.cache.add_pod(pod)

    def on_pod_update(self, old: Pod, new: Pod,
                      arrived: Optional[float] = None) -> None:
        if new.node_name:
            if self.cache.get_pod(new.key) is not None and not self.cache.is_assumed(new.key):
                self.cache.update_pod(new)
            else:
                self._confirm(new)
            # label changes on bound pods can unblock affinity waiters
            # (eventhandlers.go moves pods on assigned-pod updates)
            self.queue.move_all_to_active(self.clock())
        elif self.responsible_for(new):
            if self.cache.get_pod(new.key) is not None:
                # skipPodUpdate (eventhandlers.go:347): the pod is assumed
                # and this is the echo of a write that came before its
                # Binding (a published nomination): its queue entry is
                # spent, re-admitting it would only hand a later wave a pod
                # that is already placed
                return
            self.queue.update(new, now=self.clock() if arrived is None
                              else arrived)

    def on_pod_delete(self, pod: Pod) -> None:
        if pod.node_name:
            if self.cache.get_pod(pod.key) is not None:
                self.cache.remove_pod(pod.key)
            # freed resources may unblock pending pods (eventhandlers.go:222)
            self.queue.move_all_to_active(self.clock())
        else:
            self.queue.delete(pod.key)
            self.volume_waiting.discard(pod.key)
            # a pod parked in the Permit waiting map is assumed in the cache;
            # deletion must unwind that state, not leave it to expire into a
            # requeue of a pod that no longer exists
            meta = self._waiting_meta.pop(pod.key, None)
            if meta is not None:
                _, state, node_name, orig, _ = meta
                if self.framework is not None:
                    self.framework.pop_waiting(pod.key)
                    self.framework.run_unreserve_plugins(state, orig, node_name)
                if self.cache.is_assumed(pod.key):
                    self.cache.forget_pod(pod.key)

    def on_node_add(self, node: Node) -> None:
        self.cache.add_node(node)
        self.queue.move_all_to_active(self.clock())

    def on_node_update(self, node: Node) -> None:
        self.cache.update_node(node)
        self.queue.move_all_to_active(self.clock())

    def on_node_delete(self, name: str) -> None:
        self.cache.remove_node(name)

    # ------------------------------------------------------------------ #
    # the scheduling wave
    # ------------------------------------------------------------------ #

    def _snapshot_keys(self, pending: List[Pod]):
        # degraded mode routes the snapshot (and the interned-key scalars)
        # onto the CPU fallback device: host staging is the ground truth,
        # so nothing on this path touches the lost backend's buffers.
        # Healthy mesh serving routes them to mesh-resident sharded
        # placement instead (snapshot_mesh() is None while degraded).
        return snapshot_with_keys(self.cache, self.encoder, pending,
                                  self.base_dims,
                                  device=self.supervisor.snapshot_device(),
                                  mesh=self.supervisor.snapshot_mesh())

    def _micro_snapshot_keys(self, pending: List[Pod]):
        # micro path (ISSUE 18): sync the resident tables with an EMPTY
        # pending patch (full reuse of the double-buffer/donation
        # machinery), then graft a small fixed-P pending block for just
        # these deltas — the bulk-P pending buffer is never rebuilt for a
        # handful of pods
        return micro_snapshot_with_keys(
            self.cache, self.encoder, pending, self.base_dims,
            self._micro_p,
            device=self.supervisor.snapshot_device(),
            mesh=self.supervisor.snapshot_mesh())

    def _micro_mode(self, now: float) -> str:
        """Micro/bulk arbitration, decided once per wave after the
        governor gate: "micro" only when the ENTIRE live backlog is the
        micro lane (fresh, ungrouped, unpinned deltas) and fits one micro
        wave — anything mixed or deep is bulk work, where one full wave
        admits everything the lane holds anyway. "hold" keeps a
        not-yet-full lane waiting out the coalesce window (never when the
        window is off or the lane is full)."""
        if not self.microwave or self.extenders:
            return "bulk"
        micro_depth, active_depth, oldest = self.queue.micro_stats()
        if micro_depth == 0 or micro_depth != active_depth \
                or micro_depth > self.micro_max_batch:
            return "bulk"
        if self.micro_coalesce_s > 0.0 \
                and micro_depth < self.micro_max_batch \
                and (now - oldest) < self.micro_coalesce_s:
            return "hold"
        return "micro"

    def schedule_micro(self, now: Optional[float] = None) -> CycleStats:
        """At most one micro-wave: admit the fresh-delta lane if (and only
        if) arbitration says "micro"; empty stats otherwise. The fleet
        tick interleaves this per tenant between bulk cadences."""
        return self.schedule_pending(now, micro_only=True)

    def schedule_pending(self, now: Optional[float] = None,
                         micro_only: bool = False) -> CycleStats:
        """One wave: pump → pop batch → snapshot → device cycle → commit.

        Sequential assume semantics hold *within* the wave (the device scan
        carries the assume-state pod to pod) and *across* waves (assumed pods
        are in cache.scheduled_pods() for the next snapshot)."""
        now = self.clock() if now is None else now
        t0 = time.perf_counter()
        # per-wave phase spans (sched/telemetry.py): each mark() closes the
        # phase that just ran; the record feeds the per-operation histogram
        # and the flight-recorder ring (no-op span when KTPU_TELEMETRY=0)
        span = self.telemetry.wave_span()
        # the wave's Trace is `trace.current()` on this thread while it
        # runs: the binder, the in-process apiserver and the store add
        # their time to it as children of the phase that called them
        token = trace.activate(span.trace) if span.enabled else None
        wave = Wave(now, t0, span,
                    minor_faults0=_minor_faults() if span.enabled else 0)
        try:
            # what the wave compiles on THIS thread (a snapshot's fresh
            # patch rung, an eager scalar) is the XLA account's under
            # `wave`; the dispatch worker enters the supervisor's scope
            with xla_scope("wave", on_path=True,
                           seq=self.telemetry.recorder.next_seq(),
                           sink=self.telemetry.note_supervisor_event):
                return self._run_wave(wave, micro_only)
        except Exception:
            # a wave that DIES mid-flight is exactly the tick the flight
            # recorder exists to explain: record what ran before the raise
            # (and the supervisor events that would otherwise leak onto
            # the next wave's record), dump, and re-raise. InjectedCrash
            # (BaseException — the SIGKILL analog) punches through
            # unrecorded, as a real kill would.
            wave.stats.cycle_seconds = time.perf_counter() - t0
            span.mark("exception")
            self.telemetry.finish_wave(
                span, stats=wave.stats, engine=wave.engine, dims=wave.dims,
                extra={**wave.extra, "exception": True})
            if self.telemetry.enabled:
                self.telemetry.dump("exception")
            raise
        finally:
            if token is not None:
                trace.deactivate(token)

    def _drain_idle_events(self, span, stats, engine: str = "idle") -> None:
        """Supervisor events (a prewarm compile failure, a prober
        recovery, a breaker/mode transition) can land while the queue is
        idle; an idle/early-return/paused wave must still drain them into
        a record — auto-dumping on a trigger — instead of leaving them to
        be misattributed to the next busy wave. Event-free idle waves
        record nothing, so the ring stays signal."""
        if self.telemetry.has_pending_events():
            span.mark(engine)
            self.telemetry.finish_wave(span, stats=stats, engine=engine)

    def _no_wave(self, wave: Wave, stats: CycleStats,
                 engine: str = "idle") -> bool:
        """Finish a wave that pops nothing (paused, held, not micro-ready)."""
        wave.stats = stats
        stats.cycle_seconds = time.perf_counter() - wave.t0
        self._drain_idle_events(wave.span, stats, engine=engine)
        return False

    def _run_wave(self, wave: Wave, micro_only: bool) -> CycleStats:
        """The wave's driver: admit, decide, commit, preempt + requeue,
        record, and the early returns. What a stage may touch, and which
        phases it closes on the wave's span, is in its docstring."""
        if not self._admit(wave, micro_only):
            return wave.stats  # paused, held or empty: nothing to decide
        span, stats = wave.span, wave.stats
        if not wave.batch:
            self._schedule_extender_pods(wave)  # an extender-only wave
            stats.cycle_seconds = time.perf_counter() - wave.t0
            if self.governor is not None:
                self.governor.end_wave(wave.now, stats.attempted,
                                       stats.cycle_seconds)
            # an extender-only wave did REAL work (per-pod dispatches that
            # can degrade/abandon): it gets its own record, never "idle"
            span.mark("extenders")
            self.telemetry.finish_wave(span, stats=stats, engine="extenders",
                                       extra=wave.extra)
            return stats
        self._resolve_volumes(wave)
        if not wave.batch and not wave.ext_batch:
            # every pod popped waits on its claims: nothing to decide
            wave.engine = "volume-wait"
            span.mark("requeue")
            return self._record(wave)
        if not self._decide(wave):
            # crash-consistent wave abort: the dispatch died on BOTH
            # backends before any readback, so nothing was assumed and
            # nothing may be committed — forget the wave cleanly and
            # requeue every popped pod (attempts preserved, prompt retry:
            # the pods are fine, the backend wasn't). Without this, a
            # dispatch death mid-wave would silently LOSE the whole batch.
            stats.aborted += self._abort(wave.batch, wave.now)
            stats.aborted += self._abort(wave.ext_batch, wave.now)
            span.mark("requeue")
            stats.cycle_seconds = time.perf_counter() - wave.t0
            # the supervisor's "abandoned" event auto-dumps the ring: the
            # dead tick is reconstructable from the artifact
            self.telemetry.finish_wave(
                span, stats=stats, engine=wave.engine, dims=wave.dims,
                micro=wave.micro, extra=wave.extra)
            return stats
        failures = self._commit_stage(wave)
        self._account_gangs(wave, failures)
        self._preempt_and_requeue(wave, failures)
        span.mark("requeue")
        return self._record(wave)

    def _abort(self, pods, now: float) -> int:
        """Send `(pod, attempts)` pairs back with no verdict (attempts kept,
        prompt retry); returns how many, for the caller's counter: `aborted`
        (the wave could not run) or `requeued` (the breaker cut it short)."""
        n = 0
        for pod, attempts in pods:
            self.queue.add_prompt_retry(pod, attempts=attempts, now=now)
            n += 1
        return n

    def _schedule_extender_pods(self, wave: Wave) -> None:
        # after the batched wave: they must see its assumes
        for pod, attempts in wave.ext_batch:
            self._schedule_one_with_extenders(pod, attempts, wave.now,
                                              wave.cycle, wave.stats)

    def _admit(self, wave: Wave, micro_only: bool) -> bool:
        """Stage 1, admit: pump, governor gate, micro / bulk arbitration,
        pop, shed, extender split. False = nothing popped (paused, held,
        not micro-ready, empty) and `wave.stats` is the finished result.

        Touches the queue (pump, release_deferred, pop, park_deferred),
        the governor, the Permit waiters it expires, and the cache only
        through `cleanup` (with the size of its pass, for the record) and
        `drain_confirm_waits`. No snapshot, device,
        assume or Binding. Closes `pump` and `pop`."""
        span, now = wave.span, wave.now
        self.queue.pump(now)
        expired = self.cache.cleanup(now)
        self.expire_waiting(now)
        span.mark("pump")
        # ---- overload governor gate (sched/overload.py): mode ladder,
        # breaker pause, wave-size clamp — decided BEFORE the pop so a
        # paused wave burns no device time and pops nothing it cannot
        # commit (intents are only ever written downstream of this gate,
        # so the bind-intent ledger cannot be orphaned by a brownout) ---- #
        gov = self.governor
        decision = None
        pop_limit = self.batch_size
        if gov is not None:
            decision = gov.begin_wave(now, self.queue.depths())
            if decision.release_deferred:
                released = self.queue.release_deferred(now)
                if released:
                    self.telemetry.note_supervisor_event(
                        "deferred_release", f"{released} pods re-admitted")
            if not decision.dispatch_allowed:
                # only the transition wave records (the breaker_open event
                # rides it); a long pause must not flood the ring
                return self._no_wave(wave, CycleStats(commit_paused=1),
                                     engine="paused")
            if decision.wave_limit:
                pop_limit = min(pop_limit, decision.wave_limit)
        # ---- micro/bulk arbitration (ISSUE 18): AFTER the governor gate,
        # so a breaker pause dominates (a micro wave is still a wave) and
        # a deferred release lands in the depths the decision reads ---- #
        mode = self._micro_mode(now)
        if micro_only and mode != "micro":
            # fleet interleave probe (schedule_micro): the lane isn't
            # micro-ready — leave the backlog to the bulk cadence
            return self._no_wave(wave, CycleStats())
        if mode == "hold":
            # coalesce window open: near-simultaneous deltas share the
            # next micro dispatch instead of paying one wave each
            return self._no_wave(wave, CycleStats(), engine="hold")
        micro = wave.micro = mode == "micro"
        if micro:
            batch = self.queue.pop_micro(
                min(pop_limit, self.micro_max_batch), now=now)
        else:
            batch = self.queue.pop_batch(pop_limit, now=now)
        wave.cycle = self.queue.current_cycle()
        # what the popped pods waited for (`waits` on the wave's record):
        # pop instant less first-seen stamp, read without consuming the
        # stamps; and the Binding confirmations the informer delivered
        # since the previous wave, beside the assumes still unconfirmed
        if batch and span.enabled:
            confirm, outstanding = self.cache.drain_confirm_waits()
            wave.extra["waits"] = {
                "queue": self.telemetry.tracker.waits(
                    [p.key for p, _ in batch], self.clock()),
                "confirm": confirm}
            wave.extra["assumed_outstanding"] = outstanding
            # the size of this wave's expiry pass: assumed pods it looked
            # at, and those it dropped (their echo never came in the TTL)
            wave.extra["assumed_examined"] = self.cache.last_cleanup_examined
            wave.extra["assumed_expired"] = len(expired)
            if self.events_pending is not None:
                wave.extra["events_pending"] = self.events_pending()
        span.mark("pop")
        # ---- priority-aware shedding (SHED_LOW/TRICKLE): park sheddable
        # pods in the deferred lane — deferred, never dropped, no failure
        # verdict, no backoff escalation; high-priority pods continue
        # bit-for-bit through the unchanged pipeline ---- #
        shed_n = 0
        if decision is not None and decision.shed_below is not None and batch:
            kept: List[Tuple[Pod, int]] = []
            for pod, attempts in batch:
                if pod.priority < decision.shed_below \
                        and self.queue.park_deferred(pod, attempts, now=now):
                    shed_n += 1
                else:
                    kept.append((pod, attempts))
            batch = kept
            if shed_n:
                gov.note_shed(shed_n)
        wave.stats = CycleStats(attempted=len(batch), shed=shed_n,
                                micro=1 if micro else 0)
        # pods an extender is interested in take the per-pod extender path
        # after the batched wave (they must see the wave's assumes)
        if self.extenders:
            ext_keys = {p.key for p, _ in batch
                        if any(e.is_interested(p) for e in self.extenders)}
            wave.ext_batch = [(p, a) for p, a in batch if p.key in ext_keys]
            batch = [(p, a) for p, a in batch if p.key not in ext_keys]
        wave.batch = batch
        if not batch and not wave.ext_batch:
            self._drain_idle_events(span, wave.stats)
            return False
        return True

    def _resolve_volumes(self, wave: Wave) -> None:
        """Between admit and decide, at the head of the `snapshot` phase
        (`snapshot/volumes`): each popped pod that names
        PersistentVolumeClaims is exchanged for the copy the binder resolves
        against the listers (volume/binder.py `resolved_pod`), and a pod
        that must wait for a claim leaves the batch for unschedulableQ with
        the binder's reason. A batch without a volume pays one pass of two
        attribute reads a pod. Touches the queue (add_unschedulable) and
        `volume_waiting`; counts ride the wave's record."""
        binder = self.volume_binder
        if binder is None or not any(
                p.claims or p.volumes for p, _ in wave.batch):
            return
        from ..volume.binder import resolved_pod

        tr = trace.current()
        t0 = time.perf_counter()
        stats, waiting = wave.stats, self.volume_waiting
        kept: List[Tuple[Pod, int]] = []
        distinct: set = set()
        for pod, attempts in wave.batch:
            pod = pod.unresolved or pod
            if pod.claims:
                decision = binder.resolve(pod)
                if decision.wait:
                    waiting.add(pod.key)
                    stats.volume_waits[pod.key] = decision.reason
                    stats.unschedulable += 1
                    stats.failed_keys.append(pod.key)
                    self.queue.add_unschedulable(pod, attempts, wave.now,
                                                 cycle=wave.cycle)
                    continue
                waiting.discard(pod.key)
                pod = resolved_pod(pod, decision)
            if pod.volumes:
                stats.volume_pods += 1
                distinct.update((v.driver, v.vol_id) for v in pod.volumes)
            kept.append((pod, attempts))
        wave.batch = kept
        wave.extra["volume_pods"] = stats.volume_pods
        wave.extra["volumes_distinct"] = len(distinct)
        wave.extra["volume_waiting"] = len(waiting)
        if tr is not None:
            tr.child("volumes", time.perf_counter() - t0)

    def _decide(self, wave: Wave) -> bool:
        """Stage 2, decide: snapshot, engine plan, prewarm bookkeeping,
        the supervised dispatch with its CPU fallback, the prestage
        overlap, readback. False = the dispatch was abandoned on both
        backends and nothing may commit.

        Touches the cache only to snapshot it (and to bracket, with
        `mark_dispatch_start/done`, the time a worker holds the arrays),
        the encoder, the prewarmer, the supervisor and the device; reads
        the queue through `peek_active` only. Writes NO queue state and
        assumes / forgets NO pod: all it leaves is on the Wave. Closes
        `snapshot`, `prewarm`, `dispatch` and `readback`."""
        span, stats = wave.span, wave.stats
        pending = [p for p, _ in wave.batch]
        wave.snap, wave.keys = (
            self._micro_snapshot_keys(pending) if wave.micro
            else self._snapshot_keys(pending))
        snap = wave.snap
        if wave.stats.volume_pods:
            # the classes the engine tells the volume pods apart by: pods
            # that differ only in the names of their own volumes share one
            row = self.encoder.pod_row
            wave.extra["volume_classes"] = len(
                {row(p)[2] for p in pending if p.volumes})
        which_pinned = self._note_pins(wave, len(pending))
        span.mark("snapshot")
        # how the snapshot this wave dispatches on was produced
        # ("full" | "patch" | "cached") rides the wave's record
        wave.snap_mode = self.cache.last_snapshot_mode
        # the commit stage must map node indices through the node_order of
        # the snapshot that was ACTUALLY dispatched: a fallback re-encode
        # reflects newer cluster state (an informer event may have landed
        # between the two snapshots), and indexing the old order would
        # silently bind pods to the wrong nodes
        wave.node_order = snap.node_order
        wave.engine = plan_engine(snap.dims.has_node_name)
        gang = self._gang_of(snap) is not None
        self.prewarmer.observe(
            snap.dims, n_nodes=self.cache.node_count,
            n_existing=self.cache.pod_count,
            engine=wave.engine, extras=self._extras, gang=gang,
            mesh=snap.mesh)
        self.supervisor.note_cycle_signature(
            snap.dims, wave.engine, self._extras, gang)
        if self.microwave and not wave.micro:
            # keep the micro signature warm from the bulk cadence: the
            # first delta after a quiet period must not pay a compile on
            # the latency path
            self.prewarmer.ensure_warm(
                replace(snap.dims, P=self._micro_p, has_node_name=False),
                plan_engine(False), self._extras, False, mesh=snap.mesh)
        if self.microwave:
            # the patch-scatter ladder is the OTHER compile micro-waves
            # cannot amortize: a fresh dirty-row bucket mid-churn stalls a
            # milliseconds-sized wave ~0.5 s (state/cache.py
            # warm_patch_ladder)
            self.prewarmer.ensure_patch_ladder(self.cache, snap,
                                               mesh=snap.mesh)
        span.mark("prewarm")
        # the dispatch worker is about to hold this snapshot's arrays: the
        # prestage snapshot below must take the copy path (back buffer),
        # never donate buffers a thread is handing to XLA. EVERYTHING from
        # here to readback sits inside the try so no exception path can
        # leak the in-flight count (a leak would silently pin every later
        # mesh patch onto the copy path — the donation contract's blind
        # spot).
        self.cache.mark_dispatch_start()
        try:
            # the budget key carries the PROGRAM signature, not just the
            # shape: a gang-bearing or scan-routed wave at a warm shape
            # traces a new XLA program whose cold compile must get the
            # cold budget — keying on dims alone would misread that
            # compile as a hang and falsely mark a healthy backend lost.
            # The mesh signature is part of it: the GSPMD-partitioned
            # program is a different compile.
            handle = self.supervisor.submit(
                "cycle",
                (replace(snap.dims, has_node_name=False), wave.engine,
                 self._extras, gang, mesh_key(snap.mesh)),
                partial(self._dispatch_primary, wave),
                partial(self._dispatch_fallback, wave))
            self._prestage(wave)
            span.mark("dispatch")
            try:
                (wave.node_idx, wave.attribution, wave.gang_verdict,
                 (wave.rounds, wave.fill)) = handle.result()
            except DispatchAbandonedError:
                span.mark("readback")
                return False
            if which_pinned is not None:
                stats.pinned_unfit = wave.extra["pinned_unfit"] = int(
                    np.count_nonzero(np.asarray(
                        wave.node_idx)[:len(pending)][which_pinned] < 0))
                if wave.rounds is not None:
                    wave.extra["pin_rounds"] = int(wave.rounds)
            self._note_fill(wave, len(pending))
            span.mark("readback")
            return True
        finally:
            # the dispatch no longer holds the snapshot's arrays — the
            # next on-path mesh patch may donate the resident buffers
            self.cache.mark_dispatch_done()

    def _note_pins(self, wave: Wave, k: int):
        """At the tail of the `snapshot` phase (`snapshot/pins`): how many of
        the batch's `k` pods carry a pin, how many classes hold them, and
        the live class count, onto the wave's record, from the (class, pin)
        columns the snapshot built on the host (state/cache.py
        `pending_pins`: per stage, never per pod). A batch without a pin
        adds no field. Returns which pods are pinned ([k] bool) or None."""
        tr = trace.current()
        t0 = time.perf_counter()
        n, classes, which = self.cache.pending_pins(k)
        if n:
            wave.stats.pinned, wave.stats.pin_classes = n, classes
            wave.extra.update(pinned=n, pin_classes=classes,
                              classes=len(self.encoder.class_reg))
        if tr is not None:
            tr.child("pins", time.perf_counter() - t0)
        return which

    def _note_fill(self, wave: Wave, k: int) -> None:
        """At the tail of the `readback` phase: what the dispatch said of
        its filling classes (`AssignResult.fill`), and which of the batch's
        `k` pods ask an extended resource and were decided, onto the wave's
        record and its stats. From the class column the snapshot built on
        the host and the classes' request rows: per class, never per pod.
        A batch without a filling class / an extended resource adds no
        field."""
        if wave.fill is not None and int(wave.fill[0]):
            classes, pods, rounds = (int(x) for x in wave.fill)
            wave.stats.fill_pods = pods
            wave.extra.update(fill_classes=classes, fill_pods=pods,
                              fill_rounds=rounds)
            ecfg = self.engine_config
            if ecfg is not None and float(ecfg.w_rtc) > 0:
                # the resources of RequestedToCapacityRatio's weight map
                # that the dispatch's EngineConfig carried to the device
                wave.extra["rtc_resources"] = int(
                    np.count_nonzero(np.asarray(ecfg.rtc_w)))
        asked = self.cache.pending_extended(self.encoder, k)
        if asked:
            placed = np.asarray(wave.node_idx)[:k] >= 0
            for name, which in asked.items():
                n, fit = int(which.sum()), int((which & placed).sum())
                wave.stats.extended_pods[name] = (fit, n - fit)
            wave.extra["extended_pods"] = int(
                np.logical_or.reduce(list(asked.values())).sum())

    def _gang_of(self, snap):
        return snap.gang if self._device_gangs else None

    def _engine_call(self, tables, pending, keys, existing, gang, dims,
                     engine: str, prewarmer=None, mesh=None):
        """The wave's one call into the engine (primary and fallback):
        `(node, attribution or None, gang verdict or None, (the waves
        engine's rounds or None, its fill counts or None))`, still on the
        device."""
        explain = self.explainer is not None
        out = _schedule_batch(
            tables, pending, keys, dims.D, existing,
            has_node_name=dims.has_node_name,
            hard_weight=self.hard_pod_affinity_weight,
            ecfg=self.engine_config,
            extra_plugins=self._extras, extra_weights=self._extra_w,
            gang=gang, dims=dims, prewarmer=prewarmer, mesh=mesh,
            explain=explain, engine=engine)
        res, exp = out if explain else (out, None)
        return res.node, exp, res.gang, (res.rounds, res.fill)

    @staticmethod
    def _get_attribution(exp_dev):
        # attribution readback must never take down a wave: a zombie
        # worker's arrays may live on a dead backend
        if exp_dev is None:
            return None
        try:
            return jax.device_get(exp_dev)
        except Exception:  # noqa: BLE001 - observability, not placement
            return None

    def _read_back(self, node, exp, verdict, rounds):
        """A dispatch's results on the host: `(node_idx, attribution,
        gang verdict, (rounds, fill))`; the verdict and the counts ride the
        placements' own transfer."""
        node, verdict, rounds = jax.device_get((node, verdict, rounds))
        return node, self._get_attribution(exp), verdict, rounds

    def _dispatch_primary(self, wave: Wave):
        """The wave's dispatch on the serving backend, run by the
        supervisor's watchdog worker: engine call, then readback."""
        snap = wave.snap
        call = partial(
            self._engine_call, snap.tables, snap.pending, wave.keys,
            snap.existing, self._gang_of(snap), snap.dims, wave.engine,
            prewarmer=self.prewarmer, mesh=snap.mesh)
        tel = self.telemetry
        if not tel.enabled:
            return self._read_back(*call())
        # tier-3 device-time split (runs on the watchdog worker):
        # launch (trace + async enqueue) vs XLA execution
        # (block_until_ready) vs host readback (device_get) — the
        # encode/upload half of the ratio is the wave's snapshot span.
        # KTPU_PROFILE additionally brackets this in a jax.profiler
        # TraceAnnotation inside a lazily-started profiler trace.
        with tel.device_annotation("ktpu-wave-dispatch"):
            tp0 = time.perf_counter()
            node, exp, verdict, rounds = call()
            tp1 = time.perf_counter()
            jax.block_until_ready(node)
            tp2 = time.perf_counter()
            out = self._read_back(node, exp, verdict, rounds)
        tel.note_device_split(tp1 - tp0, tp2 - tp1,
                              time.perf_counter() - tp2, token=wave.span)
        return out

    def _dispatch_fallback(self, wave: Wave, dev, hung: bool = False):
        """Degrade the wave to the CPU backend. Preferred: ship the SAME
        encoded wave (device_put of the primary-resident arrays — the
        cheap direction when they are still reachable, e.g. an injected
        fault or a computation-only failure). A wedged runtime's buffers
        are untouchable (hung=True: a transfer would block forever with no
        watchdog) and a dead one's raise — in both cases the wave
        RE-ENCODES onto the fallback from the cache's host staging, the
        ground truth the device arrays derive from, and is planned again
        for what it then holds. No prewarmer — its executables belong to
        the primary."""
        snap, keys = wave.snap, wave.keys
        engine = wave.engine
        arrays = None
        if not hung:
            try:
                arrays = jax.device_put(
                    (snap.tables, snap.pending, keys, snap.existing,
                     self._gang_of(snap)), dev)
            except Exception:  # noqa: BLE001 - dead-source transfer
                arrays = None
        if arrays is None:
            # supervisor already marked unhealthy → snapshot_device()
            # is the fallback device: full host re-encode onto it
            snap, keys = self._snapshot_keys([p for p, _ in wave.batch])
            arrays = (snap.tables, snap.pending, keys, snap.existing,
                      self._gang_of(snap))
            engine = plan_engine(snap.dims.has_node_name)
            wave.node_order = snap.node_order
        with jax.default_device(dev):
            # degraded waves stay explainable: the chaos drill
            # reconstructs a degraded wave's failures from the flight
            # recorder, so the fallback attributes too
            return self._read_back(*self._engine_call(
                *arrays, snap.dims, engine))

    def _prestage(self, wave: Wave) -> None:
        """Double-buffered host/device overlap: the dispatch runs on the
        watchdog worker, so while the device evaluates THIS wave, the host
        interns the NEXT wave's backlog (the dominant host cost of the
        next snapshot). By the time handle.result() blocks, cycle N+1's
        pod rows are already memoized — encode of N+1 overlapped dispatch
        of N."""
        snap = wave.snap
        if self.preemptor is not None:
            from .preemption import PREEMPT_BURST

            # preemption storms compile their own fused program: warm it
            # in the background at the current dims before the first storm
            self.prewarmer.observe_preempt(snap.dims, PREEMPT_BURST,
                                           mesh=snap.mesh)
        # a micro wave skips the prestage overlap: its dispatch is
        # sub-cycle, and interning a bulk backlog under it would put
        # the bulk cost back on the latency path it exists to dodge
        backlog = [] if wave.micro \
            else self.queue.peek_active(self.batch_size)
        if not backlog:
            return
        self.encoder.intern_pods(backlog)
        if snap.mesh is not None:
            # mesh double-buffer, upload half: scatter the deltas that
            # accrued since the dispatched snapshot (informer events,
            # prior-wave confirms) into the BACK resident buffer while the
            # device evaluates THIS wave. The post-readback snapshot then
            # ships only the wave's own assumes — the delta upload of
            # cycle N+1 overlapped the dispatch of cycle N. Purely an
            # optimization: any failure here leaves the on-path snapshot
            # to do the same work after readback.
            try:
                self._snapshot_keys(backlog)
            except Exception:  # noqa: BLE001 - prestage must never
                pass           # take down the wave

    def _commit_stage(self, wave: Wave) -> List[Tuple[Pod, int]]:
        """Stage 3, commit: the wave's placements (`node_idx` through the
        dispatched snapshot's `node_order`), less stale queue entries,
        through `commit_wave`, whose touch contract is this stage's (plus
        the explainer). Returns the pods the engine placed nowhere."""
        batch, node_idx, order = wave.batch, wave.node_idx, wave.node_order
        # ---- decision provenance: render the attribution that rode the
        # dispatch (events/metrics/latest-attribution inside observe_wave;
        # the returned dict rides this wave's flight-recorder record) ---- #
        if self.explainer is not None and wave.attribution is not None:
            try:
                wave.explain = self.explainer.observe_wave(
                    batch, node_idx, wave.attribution, order, now=wave.now)
            except Exception:  # noqa: BLE001 - provenance must never
                wave.explain = None  # take down a wave
        failures: List[Tuple[Pod, int]] = []
        commits: List[Tuple[Pod, str, int]] = []
        for i, (pod, attempts) in enumerate(batch):
            ni = int(node_idx[i])
            if ni < 0:
                failures.append((pod, attempts))
                continue
            if self.cache.get_pod(pod.key) is not None:
                # skipPodSchedule: a stale queue entry for a pod that is
                # already assumed/bound (e.g. an update raced the informer
                # confirmation) — do not double-assume
                continue
            commits.append((pod, order[ni], attempts))
        self.commit_wave(commits, wave.now, wave.cycle, wave.stats,
                         span=wave.span)
        return failures

    def commit_wave(self, commits: List[Tuple[Pod, str, int]], now: float,
                    cycle: int, stats: CycleStats,
                    span=_NULL_SPAN) -> List[Tuple[Pod, str, int]]:
        """Commit one wave's `(pod, node_name, attempts)` placements: intent
        write → per pod assume, Binding, finish or roll back → one batched
        span close → intent retire. The ONE commit stage: a wave of this
        scheduler and a fleet tenant's share of a tick (fleet/server.py)
        both come through here.

        The Bindings go out in the wave's order, one awaited before the
        next, unless the binder has a window for them (`Binder.window`: a
        transport whose request sleeps through a round trip): then up to
        its width are in flight at once (`_commit_windowed`), and every
        one has been answered when this returns.

        The write-ahead intent makes the whole wave's placements durable
        in ONE CAS create before the first Binding write, and is retired
        after the last answer. A crash at pre_intent leaves nothing (pods
        re-deliver as pending), at post_intent leaves an intent recover()
        completes-or-releases, at post_bind leaves an intent recover()
        simply retires against informer truth (docs/RESILIENCE.md restart
        matrix). Returns the commits a failed intent write aborted (else
        none), for a caller that counts those its own way.

        Touches the cache (assume / finish / forget), the queue (requeue
        of what did not bind), the ledger, the binder and the governor.
        Closes `intent-write`, `bind-commit` and `retire`."""
        aborted: List[Tuple[Pod, str, int]] = []
        try:
            intent = self._write_intent(cycle, commits)
        except Exception:  # noqa: BLE001 - ledger storage unavailable
            # no durable intent → no Binding may commit (the write-ahead
            # contract). The pods are fine: prompt-requeue the would-be
            # commits, crash-consistently like an abandoned dispatch.
            stats.aborted += self._abort(
                ((p, a) for p, _node, a in commits), now)
            aborted, commits, intent = commits, [], None
        span.mark("intent-write")
        bound_keys: List[str] = []
        bind_times: List[float] = []
        open_window = getattr(self.binder, "window", None)
        window = open_window() \
            if open_window is not None and len(commits) > 1 else None
        if window is None:
            self._commit_in_turn(commits, now, cycle, stats, bound_keys,
                                 bind_times)
        else:
            self._commit_windowed(window, commits, now, cycle, stats,
                                  bound_keys, bind_times)
        # e2e watch→bind spans close in ONE batched call per wave (the
        # per-pod scalar path was most of the measured telemetry
        # overhead); the clock reading is the end of the commit loop —
        # within one wave the per-commit readings it replaces differ by
        # commit-tail microseconds, and deterministic per-tick clocks are
        # constant across a wave, so virtual latencies are unchanged
        if bound_keys:
            self.telemetry.record_bound_many(bound_keys, self.clock())
        BINDING_DURATION.observe_many(bind_times)
        span.mark("bind-commit")
        self._retire_intent(intent)
        span.mark("retire")
        return aborted

    def _commit_in_turn(self, commits, now: float, cycle: int,
                        stats: CycleStats, bound_keys: List[str],
                        bind_times: List[float]) -> None:
        """`commit_wave`'s loop, each Binding awaited before the next pod's
        turn, all on this thread: a binder without a window."""
        gov = self.governor
        commit = self._commit
        for ci, (pod, node_name, attempts) in enumerate(commits):
            if gov is not None and not gov.commit_allowed():
                # the breaker OPENED mid-wave (this wave's own commits
                # tripped it): stop burning the commit path — the rest of
                # the wave requeues promptly, no failure verdict. The
                # intent stays valid (write-ahead covers the whole wave;
                # unbound entries replay safely against informer truth)
                # and is retired by the caller as usual.
                stats.requeued += self._abort(
                    ((p, a) for p, _node, a in commits[ci:]), now)
                break
            commit(pod, node_name, attempts, now, cycle, stats,
                   latency_keys=bound_keys, bind_times=bind_times)

    def _commit_windowed(self, window, commits, now: float, cycle: int,
                         stats: CycleStats, bound_keys: List[str],
                         bind_times: List[float]) -> None:
        """`commit_wave`'s loop with up to `window.width` Binding writes in
        flight (sched/server.py `BindWindow`). This thread still assumes
        each pod, runs its Reserve, Permit, PreBind and Bind plugins, feeds
        the governor and finishes or rolls back each pod, in the order the
        answers come; only the binder's write is another thread's. It
        hands out until the window is full, gathers an answer, settles
        that pod, hands out the next; after the last hand-out it gathers
        the rest, so nothing of the wave is outstanding when it returns.

        The breaker is asked before each hand-out and fed at each gather
        with that write's own seconds: once it opens nothing more goes out,
        what is in flight (under `width`) is gathered and settled, the rest
        requeues as in `_commit_in_turn`.

        On the wave's Trace `bind-call` stays this thread's WALL seconds,
        a call a Binding: a write's hand-out and the wait in which its
        answer came. What the writes filed on their own threads is grafted
        below it at the end (`http.request` and its `wire` / `codec`:
        request-seconds, which pass their parent's wall seconds as soon as
        two overlap). `stats.bind_request_s` over `stats.bind_window_s`
        says how many were in flight on average."""
        gov = self.governor
        tr = trace.current()
        traced = tr is not None
        pc = time.perf_counter
        width = window.width
        held: Dict[int, Tuple] = {}   # handed out, unanswered, by position
        request_s = 0.0
        n, nxt = len(commits), 0

        def settle(ok, seconds, call_s, answered, state, pod, node_name,
                   attempts) -> None:
            if traced:
                tr.child("bind-call", call_s)
            self._settle(ok, seconds, state, pod, node_name, attempts, now,
                         cycle, stats, bound_keys, bind_times)
            if traced:
                tr.child("finish", pc() - answered)

        t_first = pc()
        while nxt < n or held:
            while nxt < n and len(held) < width:
                if gov is not None and not gov.commit_allowed():
                    # as in `_commit_in_turn`; those in flight are gathered
                    # below
                    stats.requeued += self._abort(
                        ((p, a) for p, _node, a in commits[nxt:]), now)
                    n = nxt
                    break
                pod, node_name, attempts = commits[nxt]
                ta0 = pc()
                go, state = self._reserve(pod, node_name, attempts, now,
                                          cycle, stats)
                tb0 = pc()
                if traced:
                    tr.child("assume", tb0 - ta0)
                if go:
                    ok = self._bind_hooks(state, pod, node_name, None)
                    if ok is None:
                        window.submit(nxt, pod, node_name, traced)
                        held[nxt] = (state, pod, node_name, attempts,
                                     pc() - tb0)
                    else:   # a plugin bound it, or refused: no write
                        tb1 = pc()
                        settle(ok, tb1 - tb0, tb1 - tb0, tb1, state, pod,
                               node_name, attempts)
                nxt += 1
            if held:
                tg0 = pc()
                tag, ok, seconds = window.gather()
                tb1 = pc()
                *whose, handing = held.pop(tag)
                request_s += seconds
                settle(ok, seconds, handing + tb1 - tg0, tb1, *whose)
        stats.bind_request_s += request_s
        stats.bind_window_s += pc() - t_first
        if traced:
            for children in window.spans():
                tr.graft("bind-call", children)

    def _account_gangs(self, wave: Wave,
                       failures: List[Tuple[Pod, int]]) -> None:
        """The gang loop's verdict (ops/gang.py), on a gang-bearing wave
        only: onto the wave's stats (the `scheduler_gang_*` counters), its
        record (`gang_rounds`, `gang_groups`, `gang_groups_rejected`) and,
        for every member of a refused group, why, for its FailedScheduling
        Event. Group ids are looked up now: they are those of the snapshot
        that was dispatched last, and a refused group's bound count is not
        moved by this wave's commits. Touches stats and the record only."""
        verdict = wave.gang_verdict
        if verdict is None:
            return
        stats = wave.stats
        rejected = verdict.rejected
        stats.gang_rounds = int(verdict.rounds)
        stats.gang_groups = int(verdict.groups)
        stats.gang_groups_rejected = int(rejected.sum())
        wave.extra.update(gang_rounds=stats.gang_rounds,
                          gang_groups=stats.gang_groups,
                          gang_groups_rejected=stats.gang_groups_rejected)
        if not stats.gang_groups_rejected:
            return
        # every member of a refused group is among the failures
        members: Dict[str, List[Pod]] = {}
        for pod, _ in failures:
            if pod.pod_group:
                members.setdefault(pod.group_key, []).append(pod)
        enc = self.encoder
        for key, pods in members.items():
            g = enc.pod_groups.get(key)
            if not (0 <= g < len(rejected) and rejected[g]):
                continue
            least = enc.group_min.get(g, 0)
            need = max(least - self.cache.group_bound_count(key), 0)
            if len(pods) < need:
                msg = (f"pod group {key}: {len(pods)} of the {need} members "
                       f"still needed are pending (min-available {least}); "
                       "none placed")
            else:
                msg = (f"pod group {key}: {int(verdict.placed[g])} of the "
                       f"{need} members still needed fit a node "
                       f"(min-available {least}); none placed")
            for pod in pods:
                stats.gang_refusals[pod.key] = msg

    def _preempt_and_requeue(self, wave: Wave,
                             failures: List[Tuple[Pod, int]]) -> None:
        """Stage 4, preempt + requeue: the preemption pass, AFTER commits
        and against ONE fresh snapshot so the what-if sees pods assumed
        earlier in this very wave (otherwise a preemptor could evict
        victims for space the wave already consumed) — the whole burst in
        a single fused dispatch (sched/preemption.py preempt_burst), not
        one snapshot+dispatch per pod; the verdict for the rest; then the
        extender's pods, one by one.

        Touches the cache (snapshot; nominate / evict in the preemptor;
        the extender pods' assumes), the queue (add_unschedulable), the
        device and the apiserver (evictions, the extender pods'
        Bindings). Its time is the `requeue` phase the driver closes, with
        `requeue/snapshot` and `requeue/preempt` (`what-if`, `nominate`,
        `evict`) beneath it; the pass's counts (`preempt_*`) ride the
        wave's record."""
        now, stats = wave.now, wave.stats
        handled_keys: set = set()
        if failures and self.preemptor is not None:
            # gang pods never preempt individually: evicting victims to place
            # ONE member of a group whose admission is all-or-nothing would
            # trade running pods for a pod that may never commit (the
            # coscheduling ecosystems gate preemption on the whole group)
            eligible = [(p, a) for p, a in failures if not p.pod_group]
            if eligible:
                tr = trace.current()
                tok = tr.begin("snapshot") if tr is not None else None
                tp0 = time.perf_counter()
                fresh = self.cache.snapshot(
                    self.encoder, [p for p, _ in failures], self.base_dims,
                    extra_intern=(UNSCHEDULABLE_TAINT_KEY,),
                    device=self.supervisor.snapshot_device(),
                    mesh=self.supervisor.snapshot_mesh(),
                )
                tp1 = time.perf_counter()
                if tr is not None:
                    tr.end(tok, tp1 - tp0)
                    tok = tr.begin("preempt")
                handled_keys = self.preemptor.preempt_burst(
                    self, eligible, fresh, now)
                if tr is not None:
                    tr.end(tok, time.perf_counter() - tp1)
                wave.extra.update(self.preemptor.last_pass)
        for pod, attempts in failures:
            if pod.key in handled_keys:
                continue
            stats.unschedulable += 1
            stats.failed_keys.append(pod.key)
            self.queue.add_unschedulable(pod, attempts, now,
                                         cycle=wave.cycle)
        self._schedule_extender_pods(wave)

    def _record(self, wave: Wave) -> CycleStats:
        """Stage 5, record: the governor's end-of-wave reading, the micro
        counter, the process's minor faults over the wave, the watch plane
        since the previous wave, and the wave's flight-recorder record.
        Touches telemetry only."""
        stats = wave.stats
        stats.cycle_seconds = time.perf_counter() - wave.t0
        if self.governor is not None:
            # micro=True keeps the ingest estimate fed but fences micro
            # timings out of the slow-streak/wave-sizing control loop —
            # sub-cycle micro waves say nothing about bulk deadlines
            self.governor.end_wave(wave.now, stats.attempted,
                                   stats.cycle_seconds, micro=wave.micro)
        if wave.micro:
            self.micro_waves += 1
            MICRO_WAVES.inc(scheduler=self.scheduler_name)
        extra = {"snapshot_mode": wave.snap_mode, **wave.extra}
        if stats.bind_window_s:
            extra["bind_request_s"] = round(stats.bind_request_s, 6)
            extra["bind_window_s"] = round(stats.bind_window_s, 6)
        if wave.span.enabled:
            extra["minor_faults"] = _minor_faults() - wave.minor_faults0
            if self.watch_plane is not None:
                extra.update(self.watch_plane())
        if wave.explain:
            extra["explain"] = wave.explain
        self.telemetry.finish_wave(
            wave.span, stats=stats, engine=wave.engine, dims=wave.dims,
            micro=wave.micro, extra=extra)
        return stats

    def _schedule_one_with_extenders(
        self, pod: Pod, attempts: int, now: float, cycle: int, stats: CycleStats
    ) -> None:
        """Per-pod path with extender round-trips: lattice mask+score → extender
        Filter per extender (generic_scheduler.go:547-574) → extender Prioritize
        rescaled ×weight×(MaxNodeScore/MaxExtenderPriority) (:834-869) →
        selectHost → assume → bind (extender Bind if one offers it, :397)."""
        from ..extender.client import ExtenderError
        from .cycle import _scores

        if self.cache.get_pod(pod.key) is not None:
            return  # stale queue entry (skipPodSchedule)

        snap, keys = self._snapshot_keys([pod])
        # one dispatch: infeasible nodes are -inf in the score matrix; the
        # extender path must see the SAME composed scores as the fused path
        from ..ops.lattice import default_engine_config

        extras, extra_w = self._extras, self._extra_w
        # the feasible/score iteration below must walk the node_order (and
        # use the D) of the snapshot that actually dispatched — a fallback
        # re-encode reflects newer cluster state (see the wave path)
        score_ctx = {"node_order": snap.node_order, "D": snap.dims.D}

        def _score_on(args, D):
            tb, pe, ky, ex = args
            return jax.device_get(_scores(
                tb, pe, ky, D, ex,
                jnp.float32(self.hard_pod_affinity_weight),
                self.engine_config or default_engine_config(),
                extras, extra_w))[0]

        def _score_fallback(dev, hung=False):
            args = None
            if not hung:
                try:
                    args = jax.device_put(
                        (snap.tables, snap.pending, keys, snap.existing),
                        dev)
                except Exception:  # noqa: BLE001 - dead-source transfer
                    args = None
            if args is None:
                # host re-encode onto the fallback (same ladder as the
                # wave path; supervisor is unhealthy here)
                fsnap, fkeys = self._snapshot_keys([pod])
                args = (fsnap.tables, fsnap.pending, fkeys, fsnap.existing)
                score_ctx["node_order"] = fsnap.node_order
                score_ctx["D"] = fsnap.dims.D
            with jax.default_device(dev):
                return _score_on(args, score_ctx["D"])

        try:
            raw = self.supervisor.run(
                "scores",
                (replace(snap.dims, has_node_name=False), extras,
                 mesh_key(snap.mesh)),
                lambda: _score_on((snap.tables, snap.pending, keys,
                                   snap.existing), snap.dims.D),
                _score_fallback)
        except DispatchAbandonedError:
            # same crash-consistency contract as the wave path: nothing was
            # assumed — requeue promptly instead of losing the pod
            stats.aborted += 1
            self.queue.add_prompt_retry(pod, attempts=attempts, now=now)
            return

        nodes_by_name = {n.name: n for n in self.cache.nodes()}
        feasible: List[str] = []
        combined: Dict[str, float] = {}
        for i, name in enumerate(score_ctx["node_order"]):
            if raw[i] != float("-inf"):
                feasible.append(name)
                combined[name] = float(raw[i])

        failed = False
        for ext in self.extenders:
            if not ext.is_interested(pod):
                continue
            try:
                names, _ = ext.filter(pod, [nodes_by_name[n] for n in feasible])
                allowed = set(names)
                feasible = [n for n in feasible if n in allowed]
                escore, weight = ext.prioritize(
                    pod, [nodes_by_name[n] for n in feasible])
                for n in feasible:
                    # extender scores 0-10 rescale to the 0-100 plugin range
                    combined[n] = combined.get(n, 0.0) + escore.get(n, 0) * weight * 10.0
            except ExtenderError:
                if getattr(ext.config, "ignorable", False):
                    continue  # extender.go:153-157 Ignorable
                failed = True
                break
            if not feasible:
                break

        if failed or not feasible:
            # FitError → preemption, same as the batched path (scheduler.go:629)
            handled = False
            if not failed and self.preemptor is not None:
                fresh = self.cache.snapshot(
                    self.encoder, [pod], self.base_dims,
                    extra_intern=(UNSCHEDULABLE_TAINT_KEY,),
                    device=self.supervisor.snapshot_device(),
                    mesh=self.supervisor.snapshot_mesh(),
                )
                handled = self.preemptor.try_preempt(self, pod, attempts, fresh, now)
            if not handled:
                stats.unschedulable += 1
                stats.failed_keys.append(pod.key)
                self.queue.add_unschedulable(pod, attempts, now, cycle=cycle)
            return

        best = max(feasible, key=lambda n: combined.get(n, float("-inf")))
        binder_ext = next(
            (e for e in self.extenders if e.is_binder and e.is_interested(pod)), None)
        try:
            intent = self._write_intent(cycle, [(pod, best, attempts)])
        except Exception:  # noqa: BLE001 - same contract as the wave path
            stats.aborted += 1
            self.queue.add_prompt_retry(pod, attempts=attempts, now=now)
            return
        self._commit(pod, best, attempts, now, cycle, stats, binder_ext=binder_ext)
        self._retire_intent(intent)

    # ------------------------------------------------------------------ #
    # exactly-once plumbing: intent ledger + fencing + crash recovery
    # (sched/ledger.py; docs/RESILIENCE.md §Restart/HA)
    # ------------------------------------------------------------------ #

    def _fence_token(self) -> int:
        """The current fencing token (lease generation). 0 without leader
        election — the apiserver only fences when a Lease exists."""
        return int(self.fence_source()) if self.fence_source is not None \
            else 0

    def _write_intent(self, cycle: int,
                      commits: Sequence[Tuple[Pod, str, int]]):
        """Durably record the wave's placements before any Binding write
        (no-op without a ledger). Crashpoints bracket the write so the kill
        matrix can die exactly before/after it."""
        if self.ledger is None or not commits:
            return None
        from ..utils import faultline

        faultline.crashpoint("pre_intent")
        intent = self.ledger.write_intent(
            cycle=cycle, token=self._fence_token(),
            bindings={p.key: node for p, node, _ in commits})
        faultline.crashpoint("post_intent")
        return intent

    def _retire_intent(self, intent) -> None:
        if intent is None:
            return
        from ..utils import faultline

        faultline.crashpoint("post_bind")
        try:
            self.ledger.retire(intent)
        except Exception:  # noqa: BLE001 - a failed retire is SAFE: the
            # next recover() replays the record against informer truth and
            # finds every entry already settled — never double-bound
            pass

    def node_fits(self, pod: Pod, node_name: str) -> bool:
        """Host-side feasibility for intent replay: does `node_name` still
        hold the pod's requests given everything bound/assumed there NOW?
        Deliberately resource-only (the cheap, always-available subset,
        evaluated by the executable oracle api/semantics.pod_fits_resources):
        replay prefers completing a crashed leader's decision when it is
        still sane, and releases to the queue — where the full device
        evaluation reruns — when in doubt."""
        from ..api.semantics import pod_fits_resources

        node = self.cache.get_node(node_name)
        if node is None:
            return False
        occupants = self.cache.pods_on_node(node_name)
        used_sc: Dict[str, int] = {}
        for p in occupants:
            for k, v in p.requests.scalars:
                used_sc[k] = used_sc.get(k, 0) + v
        from ..api.types import Resources

        used = Resources(
            milli_cpu=sum(p.requests.milli_cpu for p in occupants),
            memory_kib=sum(p.requests.memory_kib for p in occupants),
            ephemeral_kib=sum(p.requests.ephemeral_kib for p in occupants),
            scalars=tuple(sorted(used_sc.items())))
        ok, _fails = pod_fits_resources(pod, node, used, len(occupants))
        return ok

    def commit_recovered(self, pod: Pod, node_name: str,
                         now: Optional[float] = None) -> bool:
        """Complete one replayed intent entry: assume → fenced bind →
        finish_binding, with the plain rollback on refusal (most commonly
        the apiserver's already-assigned guard when our informer lagged the
        crashed leader's committed write).

        Only valid on the PLAIN pipeline: with a framework (Reserve/Permit/
        PreBind gates) or extenders configured, the crashed wave's intent
        was written BEFORE those points ran, so completing the bind here
        would commit a placement a plugin might have refused — refuse
        instead, and let the release path re-run the full gauntlet."""
        now = self.clock() if now is None else now
        if self.framework is not None or self.extenders:
            return False  # gates must re-run: release → full pipeline
        if self.cache.get_pod(pod.key) is not None:
            return False  # already assumed/bound in this incarnation
        self.cache.assume_pod(pod, node_name)
        try:
            ok = bool(self.binder.bind(pod, node_name))
        except Exception:  # noqa: BLE001 - a raising binder is a refusal
            ok = False
        if ok:
            self.cache.finish_binding(pod.key, now)
            # close the span BEFORE queue.delete discards the stamp (the
            # recovered pod may still sit in a queue lane on this side)
            self.telemetry.record_bound(pod.key, now)
            self.queue.delete(pod.key)
            return True
        self.cache.forget_pod(pod.key)
        return False

    def recover(self, lookup=None, now: Optional[float] = None):
        """Startup/takeover reconciliation: replay every unretired bind
        intent against informer truth (sched/ledger.py replay — the full
        decision table lives there). `lookup(pod_key)` must return the
        live Pod (node_name = the apiserver's view) or None; the default
        reads this scheduler's own cache+queue, which suffices once the
        informers have synced. Returns a RecoveryReport (None w/o ledger)."""
        if self.ledger is None:
            return None
        if lookup is None:
            lookup = self._cache_lookup
        return self.ledger.replay(self, lookup, now=now)

    def _cache_lookup(self, pod_key: str) -> Optional[Pod]:
        pod = self.cache.get_pod(pod_key)
        if pod is not None:
            return pod
        # not bound: an unbound pending pod lives in SOME queue lane —
        # including backoff/unschedulable (a pre-crash failure verdict
        # must not read as "pod deleted")
        return self.queue.get_pod(pod_key)

    def warm_standby(self) -> None:
        """One warm-standby beat (the non-leading half of HA failover): keep
        the encoder/staging/device state and the prewarmed executables HOT
        from informer truth without popping, assuming, or binding anything.
        A takeover then skips cold-compile and full re-ingest — the first
        led wave patches an already-resident snapshot and hits a warm
        executable. Strictly read-only against queue and apiserver."""
        backlog = self.queue.peek_active(self.batch_size)
        self.encoder.intern_pods(backlog)
        snap, _keys = self._snapshot_keys(backlog)
        wave_engine = plan_engine(snap.dims.has_node_name)
        extras = self._extras
        gang = self._gang_of(snap) is not None
        # compile the signature the first led wave WILL dispatch (idempotent
        # per signature), and keep the growth-boundary lookahead running so
        # a takeover into a growing cluster doesn't stall either
        self.prewarmer.ensure_warm(snap.dims, wave_engine, extras, gang,
                                   mesh=snap.mesh)
        self.prewarmer.observe(
            snap.dims, n_nodes=self.cache.node_count,
            n_existing=self.cache.pod_count,
            engine=wave_engine, extras=extras, gang=gang, mesh=snap.mesh)

    # ------------------------------------------------------------------ #
    # commit path: assume → Reserve → Permit → PreBind → Bind → PostBind
    # (scheduler.go:660-762)
    # ------------------------------------------------------------------ #

    def _commit(
        self,
        pod: Pod,
        node_name: str,
        attempts: int,
        now: float,
        cycle: int,
        stats: CycleStats,
        binder_ext: Optional["object"] = None,
        latency_keys: Optional[List[str]] = None,
        bind_times: Optional[List[float]] = None,
    ) -> None:
        """One pod through the whole sequence on this thread, its Binding
        awaited: `_reserve` → `_run_bind` → `_settle`."""
        # the inside of a Binding, as children of the phase that called
        # (`bind-commit/assume`, `/bind-call`, `/finish`): one clock read
        # at each seam, aggregated per wave on the wave's Trace
        tr = trace.current()
        ta0 = time.perf_counter()
        go, state = self._reserve(pod, node_name, attempts, now, cycle,
                                  stats, binder_ext)
        if not go:
            return
        tb0 = time.perf_counter()
        if tr is not None:
            tr.child("assume", tb0 - ta0)
            tok = tr.begin("bind-call")
        ok = self._run_bind(state, pod, node_name, binder_ext)
        tb1 = time.perf_counter()
        if tr is not None:
            tr.end(tok, tb1 - tb0)
        self._settle(ok, tb1 - tb0, state, pod, node_name, attempts, now,
                     cycle, stats, latency_keys, bind_times)
        if tr is not None:
            tr.child("finish", time.perf_counter() - tb1)

    def _reserve(self, pod: Pod, node_name: str, attempts: int, now: float,
                 cycle: int, stats: CycleStats,
                 binder_ext: Optional["object"] = None) -> Tuple[bool, Any]:
        """assume → Reserve → Permit (scheduler.go:660-707). `(True, the
        pod's CycleState or None)` when its Binding is next; `(False, _)`
        when a plugin refused it (rolled back) or Permit parked it."""
        fw = self.framework
        self.cache.assume_pod(pod, node_name)
        self.queue.delete_nominated(pod.key)
        if fw is None:
            return True, None
        from ..framework.interface import Code, CycleState

        state = CycleState()
        st = fw.run_reserve_plugins(state, pod, node_name)  # scheduler.go:669
        if st is not None and not st.is_success:
            self._roll_back(state, pod, node_name, attempts, now, cycle,
                            stats, as_bind_error=False)
            return False, state
        # Pre-register the waiting metadata BEFORE the permit plugins run:
        # run_permit_plugins publishes a WAITing pod in the framework's
        # cross-thread waiting map, and a permit controller may allow +
        # complete_waiting() in that window — the meta must already be
        # there to consume. Keep the ORIGINAL (unstamped) pod for
        # requeue-on-failure — the cached copy carries node_name and
        # would pin retries to this node. dict.pop is the atomic
        # exactly-one-consumer handoff.
        self._waiting_meta[pod.key] = (attempts, state, node_name,
                                       pod, binder_ext)
        st = fw.run_permit_plugins(state, pod, node_name)   # scheduler.go:707
        if st.code == Code.WAIT:
            return False, state  # parked (or completed by a racing allow)
        self._waiting_meta.pop(pod.key, None)
        if not st.is_success:
            self._roll_back(state, pod, node_name, attempts, now, cycle,
                            stats, as_bind_error=False)
            return False, state
        return True, state

    def _roll_back(self, state, pod: Pod, node_name: str, attempts: int,
                   now: float, cycle: int, stats: CycleStats,
                   as_bind_error: bool) -> None:
        # scheduler.go:717,732 — Unreserve + ForgetPod + requeue
        if self.framework is not None and state is not None:
            self.framework.run_unreserve_plugins(state, pod, node_name)
        self.cache.forget_pod(pod.key)
        if as_bind_error:
            stats.bind_errors += 1
        else:
            stats.unschedulable += 1
        stats.failed_keys.append(pod.key)
        self.queue.add_unschedulable(pod, attempts, now, cycle=cycle)

    def _settle(self, ok: bool, seconds: float, state, pod: Pod,
                node_name: str, attempts: int, now: float, cycle: int,
                stats: CycleStats, latency_keys: Optional[List[str]],
                bind_times: Optional[List[float]]) -> None:
        """A Binding's answer, on the thread that commits the wave: the
        governor's note, then finish + PostBind, or the rollback."""
        if self.governor is not None:
            # commit-path breaker feed: outcome + wall latency of the
            # Binding write (wall time, not the injected clock — the SLO
            # is about real apiserver round-trips)
            self.governor.note_commit(ok, seconds)
        if not ok:
            self._roll_back(state, pod, node_name, attempts, now, cycle,
                            stats, as_bind_error=True)
            return
        # scheduler_binding_duration_seconds: one sample per Binding
        # written, fed per wave where the caller batches
        if bind_times is not None:
            bind_times.append(seconds)
        else:
            BINDING_DURATION.observe(seconds)
        self.cache.finish_binding(pod.key, now)
        # e2e watch→bind: close the pod's first-seen span (stamped at
        # queue admission) in the scheduler's clock domain — at the
        # clock's CURRENT reading, not the wave-entry `now`: the
        # binding wave's own snapshot/dispatch/commit time is part of
        # the span being claimed (under a per-tick deterministic
        # clock the two readings coincide, so virtual latencies are
        # unchanged). Wave callers pass `latency_keys` to close the
        # whole wave's spans in one batched call instead (the per-pod
        # scalar path was most of the measured telemetry overhead).
        if latency_keys is not None:
            latency_keys.append(pod.key)
        else:
            self.telemetry.record_bound(pod.key, self.clock())
        stats.scheduled += 1
        stats.assignments[pod.key] = node_name
        if self.framework is not None and state is not None:
            self.framework.run_post_bind_plugins(state, pod, node_name)

    def _bind_hooks(self, state, pod: Pod, node_name: str,
                    binder_ext: Optional["object"]) -> Optional[bool]:
        """PreBind and what may bind in the binder's place, a Bind plugin
        or a binder extender (scheduler.go:727-741): the Binding's outcome
        where one of them settled it, None where the binder's write is
        still to make. A raising plugin is a refusal."""
        fw = self.framework
        try:
            if fw is not None and state is not None:
                from ..framework.interface import Code

                st = fw.run_pre_bind_plugins(state, pod, node_name)
                if st is not None and not st.is_success:
                    return False
                bst = fw.run_bind_plugins(state, pod, node_name)
                if bst.code != Code.SKIP:
                    return bst.is_success
            if binder_ext is not None:
                binder_ext.bind(pod, node_name)
                return True
        except Exception:
            return False
        return None

    def _run_bind(self, state, pod: Pod, node_name: str,
                  binder_ext: Optional["object"]) -> bool:
        """The shared PreBind → Bind tail of the commit sequence
        (scheduler.go:727-741). Everything — including raising plugins — is
        contained here so both callers roll back identically on failure."""
        ok = self._bind_hooks(state, pod, node_name, binder_ext)
        if ok is not None:
            return ok
        try:
            return self.binder.bind(pod, node_name)
        except Exception:
            return False

    def complete_waiting(self, key: str, now: Optional[float] = None) -> bool:
        """Finish the bind for a pod released from the Permit waiting map
        (frameworkHandle.IterateOverWaitingPods → Allow flow). Call after
        framework.allow_waiting_pod returns True."""
        now = self.clock() if now is None else now
        meta = self._waiting_meta.pop(key, None)
        if meta is None:
            return False
        attempts, state, node_name, pod, binder_ext = meta
        if self.cache.get_pod(key) is None:
            return False
        fw = self.framework
        ok = self._run_bind(state, pod, node_name, binder_ext)
        if ok:
            self.cache.finish_binding(key, now)
            self.telemetry.record_bound(key, now)
            fw.run_post_bind_plugins(state, pod, node_name)
            return True
        self.waiting_bind_errors += 1
        fw.run_unreserve_plugins(state, pod, node_name)
        self.cache.forget_pod(key)
        self.queue.add_unschedulable(pod, attempts, now, cycle=self.queue.current_cycle())
        return False

    def reject_waiting(self, key: str, now: Optional[float] = None) -> bool:
        """Reject a Permit-waiting pod (WaitingPod.Reject flow): unreserve,
        forget the assume, requeue for retry."""
        if self.framework is None:
            return False
        now = self.clock() if now is None else now
        w = self.framework.pop_waiting(key)
        meta = self._waiting_meta.pop(key, None)
        if w is None and meta is None:
            return False
        attempts = meta[0] if meta else 0
        pod = meta[3] if meta else w.pod
        state = meta[1] if meta else w.state
        node_name = meta[2] if meta else w.node_name
        self.framework.run_unreserve_plugins(state, pod, node_name)
        if self.cache.is_assumed(key):
            self.cache.forget_pod(key)
        self.queue.add_unschedulable(pod, attempts, now,
                                     cycle=self.queue.current_cycle())
        return True

    def expire_waiting(self, now: Optional[float] = None) -> int:
        """Reject Permit-waiting pods past their deadline: unreserve, forget,
        requeue (waiting_pods_map timeout semantics)."""
        if self.framework is None:
            return 0
        now = self.clock() if now is None else now
        expired = self.framework.expire_waiting(now)
        for w in expired:
            meta = self._waiting_meta.pop(w.pod.key, None)
            attempts = meta[0] if meta else 0
            pod = meta[3] if meta else w.pod  # original unstamped pod
            self.framework.run_unreserve_plugins(w.state, pod, w.node_name)
            if self.cache.is_assumed(w.pod.key):
                self.cache.forget_pod(w.pod.key)
            self.queue.add_unschedulable(pod, attempts, now,
                                         cycle=self.queue.current_cycle())
        return len(expired)

    def run_until_idle(self, max_waves: int = 100) -> CycleStats:
        """Drive waves until the active queue drains (integration-test helper;
        the production loop is wait.Until(scheduleOne) — scheduler.go:425-431)."""
        total = CycleStats()
        for _ in range(max_waves):
            s = self.schedule_pending()
            total.attempted += s.attempted
            total.scheduled += s.scheduled
            total.unschedulable += s.unschedulable
            total.bind_errors += s.bind_errors
            total.aborted += s.aborted
            total.shed += s.shed
            total.requeued += s.requeued
            total.commit_paused += s.commit_paused
            total.assignments.update(s.assignments)
            if self.queue.lengths()[0] == 0:
                break
        return total
