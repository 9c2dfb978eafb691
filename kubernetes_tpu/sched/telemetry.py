"""Flight recorder + end-to-end latency telemetry for the serving scheduler.

Three tiers (ISSUE 7; docs/OBSERVABILITY.md is the operator-facing manual):

  1. **Per-pod e2e latency** — every pending pod is stamped at informer-
     ingest/queue-add time (`PodLatencyTracker`, first-seen semantics: a
     backoff requeue, a prompt retry or a crash-recovery re-admission keeps
     the ORIGINAL stamp) and recorded at Binding-commit into the
     `scheduler_pod_e2e_latency_seconds` histogram — the metric ROADMAP
     item 2's "p99 watch→bind < 100 ms" target is defined in. Stamps live
     in the *scheduler's* clock domain (the injected, possibly
     deterministic per-tick clock), so tests and the mesh/fleet
     bit-equality suites measure exact virtual latencies.
  2. **Per-wave phase spans** — `SchedulerTelemetry.wave_span()` wraps a
     `component/trace.py` Trace (injected clock) around one serving wave;
     the scheduler marks pump → pop → snapshot → prewarm → dispatch →
     readback → intent-write → bind-commit → retire, each span feeding the
     `scheduler_scheduling_duration_seconds{operation=<phase>}` histogram
     and the bounded in-memory **flight recorder ring**. Supervisor events
     (degraded / fallback / watchdog_timeout / abandoned / rewarm /
     recovery — sched/supervisor.py `event_sink`) and per-tenant fleet
     stats attach to the wave record, and the ring dumps structured JSON
     on demand (`/debug/flightrecorder`, `dump()`) and automatically on an
     abandoned dispatch, a watchdog budget violation, a tenant storm or a
     takeover — a bad tick in bench/chaos is explainable from the
     artifact, not from logs.
  3. **Device-time split** — the primary dispatch separately times XLA
     launch (trace+enqueue) vs execution (`block_until_ready`) vs readback
     (`device_get`), so host-pipeline-overlap regressions show up as a
     ratio; `KTPU_PROFILE=<dir>` additionally starts a `jax.profiler`
     trace with per-wave `TraceAnnotation` markers.

Where the chip sits idle (ISSUE 24) rides the same record, on the same
clock: `children` (what ran INSIDE a phase — `bind-commit/bind-call/
apiserver.bind/store.txn` — as `[count, total_s, max_s]` aggregates from
the wave's Trace, which is `component.trace.current()` while the wave
runs), `loop` (what the server loop did between the previous wave and
this one: `post-wave`, `lock-wait`, `batch-wait`, `idle-wait`, plus the
informer handlers' calls, waits for and holds of the server's lock; under
`loop.children` what ran inside a lap, in `children`' own form: the stages
of a server's `start` (ISSUE 37), each informer's list+replace round below
the stage that waited for it) and `waits` (how long the popped pods had
queued; how long Binding confirmations took to come back through the
informer). Every record also says what the interpreter's collector did
since the record before it: `gc_full_collections`, `gc_pause_s`,
`gc_max_pause_s`, `gc_max_pause_at` (`GcAccount`, one hook a process).

Kill switch: ``KTPU_TELEMETRY=0`` turns every tier into a no-op (the
`latency` bench stage uses it to bound telemetry overhead at <2% of the
untelemetered flagship pods/s).
"""

from __future__ import annotations

import gc
import gzip
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..component.metrics import DEFAULT_REGISTRY, Counter
from ..component.trace import Trace
from .metrics import FLIGHT_DUMPS, POD_E2E_LATENCY, SCHEDULING_DURATION

#: supervisor/tick event kinds that auto-dump the ring when they appear on
#: a wave record (the "explainable without logs" triggers of ISSUE 7),
#: most severe first — the dump is labelled with the worst event present
DUMP_TRIGGERS = ("abandoned", "watchdog_timeout", "storm", "breaker_open",
                 "degraded")

#: canonical serving-wave phase order (the scheduler marks a subset; fleet
#: ticks add stack-refresh/solo phases) — tests assert ordering against it
WAVE_PHASES = ("pump", "pop", "snapshot", "prewarm", "dispatch", "readback",
               "intent-write", "bind-commit", "retire", "requeue")

#: per-record payload caps, applied at SERIALIZATION time (snapshot/dump —
#: the in-memory ring keeps full records): a large fleet's per-tick tenant
#: map and a storm's event burst were most of FLIGHT_rNN.json's ~4.6k
#: lines per bench run. The tenant cap is overridable via
#: KTPU_FLIGHT_FLEET_CAP (bounds-checked; garbage → default).
FLIGHT_FLEET_TENANT_CAP = 8
FLIGHT_EVENT_CAP = 32


def _cap_record(rec: Dict[str, Any]) -> Dict[str, Any]:
    """A serialization-bounded copy of one wave record: the fleet map keeps
    the `tenant cap` busiest tenants (by attempted, ties by name) plus one
    aggregate "..." row summing every numeric field of the omitted rest —
    fleet-wide totals stay reconstructable from the capped form; the
    supervisor-event list keeps its head and tail around an explicit
    truncation marker. Records already under the caps pass through
    unchanged (same content, fresh dict)."""
    from ..utils.envparse import env_int

    out = dict(rec)
    fleet = out.get("fleet")
    tcap = env_int("KTPU_FLIGHT_FLEET_CAP", FLIGHT_FLEET_TENANT_CAP,
                   1, 4096)
    if isinstance(fleet, dict) and len(fleet) > tcap:
        busiest = sorted(
            fleet, key=lambda n: (-(fleet[n].get("attempted", 0)
                                    if isinstance(fleet[n], dict) else 0),
                                  str(n)))
        keep = set(busiest[:tcap])
        agg: Dict[str, Any] = {"tenants_omitted": len(fleet) - len(keep)}
        for n, v in fleet.items():
            if n in keep or not isinstance(v, dict):
                continue
            for k2, x in v.items():
                if isinstance(x, (int, float)):
                    agg[k2] = agg.get(k2, 0) + x
        capped = {n: v for n, v in fleet.items() if n in keep}
        capped["..."] = agg
        out["fleet"] = capped
    ev = out.get("supervisor_events")
    ecap = FLIGHT_EVENT_CAP
    if isinstance(ev, list) and len(ev) > ecap:
        head = ev[:max(ecap // 2, 1)]
        tail = ev[len(ev) - max(ecap - len(head) - 1, 0):]
        out["supervisor_events"] = (
            head
            + [("truncated",
                f"{len(ev) - len(head) - len(tail)} events omitted")]
            + tail)
    return out


def _write_dump(doc: Dict[str, Any], path: str) -> None:
    """Write a flight document compactly: one JSON line per wave record
    instead of `indent=1`'s line-per-scalar (which made FLIGHT_rNN.json
    ~4.6k lines per bench run). Still a single valid JSON object —
    `json.load` reconstructs it unchanged. A `.gz` path gzips the same
    bytes (KTPU_FLIGHT_GZIP policy appends the suffix)."""
    opener = (lambda p: gzip.open(p, "wt")) if path.endswith(".gz") else \
        (lambda p: open(p, "w"))
    with opener(path) as f:
        f.write("{\n")
        for k, v in doc.items():
            if k == "records":
                continue
            f.write(f" {json.dumps(k)}: {json.dumps(v)},\n")
        recs = doc.get("records", [])
        f.write(' "records": [\n')
        f.write(",\n".join("  " + json.dumps(r) for r in recs))
        f.write("\n ]\n}\n" if recs else " ]\n}\n")


class PodLatencyTracker:
    """First-seen ingest stamps, keyed by pod key, in the caller's clock
    domain. `stamp` is idempotent — requeues (backoff, prompt retry,
    crash-recovery re-admission) keep the ORIGINAL stamp, so the recorded
    latency is the true watch→bind span, not the last-retry span."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._first_seen: Dict[str, float] = {}

    def stamp(self, key: str, now: float) -> None:
        with self._mu:
            self._first_seen.setdefault(key, now)

    def first_seen(self, key: str) -> Optional[float]:
        with self._mu:
            return self._first_seen.get(key)

    def discard(self, key: str) -> None:
        """Pod deleted while pending — the span will never complete."""
        with self._mu:
            self._first_seen.pop(key, None)

    def pop_latency(self, key: str, now: float) -> Optional[float]:
        """Binding committed: consume the stamp, return the e2e span."""
        with self._mu:
            t0 = self._first_seen.pop(key, None)
        return None if t0 is None else max(now - t0, 0.0)

    def pop_latencies(self, keys, now: float) -> List[float]:
        """Batch `pop_latency`: one lock round-trip for a whole wave's
        Binding commits (never-stamped keys are skipped). The per-pod
        lock+call overhead of the scalar path was a measurable slice of
        the ≤2% telemetry budget at thousands of binds per wave."""
        out: List[float] = []
        with self._mu:
            pop = self._first_seen.pop
            for k in keys:
                t0 = pop(k, None)
                if t0 is not None:
                    out.append(max(now - t0, 0.0))
        return out

    def waits(self, keys, now: float) -> List[float]:
        """`[count, sum_s, max_s]` of `now` minus the first-seen stamp over
        `keys`, WITHOUT consuming the stamps (the pop-time read: how long
        the batch had queued; the commit-time `pop_latencies` still closes
        each span). One lock round-trip for the batch."""
        n, total, worst = 0, 0.0, 0.0
        with self._mu:
            get = self._first_seen.get
            for k in keys:
                t0 = get(k)
                if t0 is not None:
                    dt = now - t0
                    n += 1
                    total += dt
                    if dt > worst:
                        worst = dt
        return [n, round(max(total, 0.0), 6), round(worst, 6)]

    def __len__(self) -> int:
        with self._mu:
            return len(self._first_seen)


class FlightRecorder:
    """Bounded ring of wave/tick records. Append-only; `dump()` snapshots
    the ring into one structured-JSON document (optionally to a file)."""

    def __init__(self, capacity: int = 64) -> None:
        self.capacity = capacity
        self._mu = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self._seq = 0
        self.evicted = 0  # records pushed out of the ring

    def record(self, rec: Dict[str, Any]) -> Dict[str, Any]:
        with self._mu:
            self._seq += 1
            rec["seq"] = self._seq
            if len(self._ring) == self.capacity:
                self.evicted += 1
            self._ring.append(rec)
        return rec

    def records(self) -> List[Dict[str, Any]]:
        with self._mu:
            return list(self._ring)

    def snapshot(self, trigger: str) -> Dict[str, Any]:
        with self._mu:
            return {
                "trigger": trigger,
                "capacity": self.capacity,
                "evicted": self.evicted,
                "last_seq": self._seq,
                "records": [_cap_record(r) for r in self._ring],
            }


class _NullSpan:
    """No-op span when telemetry is disabled (KTPU_TELEMETRY=0)."""

    __slots__ = ()
    enabled = False

    def mark(self, name: str) -> None:  # noqa: ARG002 - interface
        pass


class _WaveSpan:
    """One serving wave's phase timeline: a component/trace.py Trace with
    the telemetry clock injected. `mark(name)` closes the phase that just
    ran; phase durations are derived from consecutive steps."""

    __slots__ = ("trace",)
    enabled = True

    def __init__(self, clock: Callable[[], float], name: str,
                 threshold: float) -> None:
        self.trace = Trace(name, clock=clock, threshold=threshold)

    def mark(self, name: str) -> None:
        self.trace.step(name)

    def phases(self) -> List[Tuple[str, float]]:
        out: List[Tuple[str, float]] = []
        prev = self.trace.start
        for ts, msg in self.trace.steps:
            out.append((msg, max(ts - prev, 0.0)))
            prev = ts
        return out


_NULL_SPAN = _NullSpan()

#: flight-recorder ring bounds for KTPU_FLIGHT_RING (a ring of 0 would
#: record nothing silently; an unbounded one defeats "bounded")
FLIGHT_RING_DEFAULT = 64
FLIGHT_RING_MIN = 1
FLIGHT_RING_MAX = 65536


def flight_ring_capacity(default: int = FLIGHT_RING_DEFAULT) -> int:
    """Bounds-checked KTPU_FLIGHT_RING parse: the flight-recorder ring
    size. Unset/empty/garbage → the default; numeric values clamp into
    [FLIGHT_RING_MIN, FLIGHT_RING_MAX] — an operator typo must degrade to
    a sane ring, never crash the scheduler or disable recording."""
    raw = os.environ.get("KTPU_FLIGHT_RING", "")
    if not raw:
        return default
    try:
        v = int(raw)
    except ValueError:
        return default
    return min(max(v, FLIGHT_RING_MIN), FLIGHT_RING_MAX)


class _Pauses:
    """Collections counted one at a time by `GcAccount`'s hook: how many,
    their seconds, and the pauses a later reader may still ask the longest
    of."""

    #: pauses kept for `since`; an instant older than the oldest kept is
    #: answered from what is kept
    PEAKS = 64

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        # (began, seconds), seconds strictly falling: a pause is dropped
        # when a later one is at least as long, so the longest pause since
        # an instant is the first kept one that ended after it
        self._peaks: deque = deque()

    def add(self, began: float, dt: float) -> None:
        self.count += 1
        self.total += dt
        peaks = self._peaks
        while peaks and peaks[-1][1] <= dt:
            peaks.pop()
        peaks.append((began, dt))
        if len(peaks) > self.PEAKS:
            peaks.popleft()

    def mark(self) -> Tuple[int, float]:
        return self.count, self.total

    def since(self, mark: Tuple[int, float],
              t: float) -> Tuple[int, float, float, float]:
        """(collections, their seconds, the longest, the instant it began)
        of those that ENDED after `t`, where the account stood at `mark`:
        a collection is counted where it stops, so one that straddles `t`
        is this interval's, pause and peak alike."""
        began, longest = next(
            (p for p in list(self._peaks) if p[0] + p[1] >= t), (0.0, 0.0))
        return self.count - mark[0], self.total - mark[1], longest, began


#: (`all`'s mark, `full`'s mark, the instant): `GcAccount.mark()`
_GcMark = Tuple[Tuple[int, float], Tuple[int, float], float]


class GcAccount:
    """What the interpreter's collector cost this process: the collections
    and the seconds from a collection's `start` to its `stop` on
    `time.perf_counter`, of every generation (`all`) and of the full ones
    (`full`), and per generation for the two Prometheus series. ONE
    `gc.callbacks` hook a process (`gc_account()`), two calls a collection.

    The hook takes no lock and touches no metric: a collection begins
    wherever an allocation does, under any lock its thread holds (a
    metric's own, while it exposes). Collections do not nest and the hook
    is the only writer, so the lists need none; the two series read them
    when they are read (`_GcSeries`)."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter) -> None:
        self.clock = clock
        self.collections = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        self.all, self.full = _Pauses(), _Pauses()
        self._began = 0.0

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._began = self.clock()
            return
        dt = self.clock() - self._began
        gen = info["generation"]
        self.collections[gen] += 1
        self.pause_s[gen] += dt
        self.all.add(self._began, dt)
        if gen == 2:
            self.full.add(self._began, dt)

    def mark(self) -> _GcMark:
        """Where the account stands now: what `since` and `children` take
        to say what happened after this instant."""
        return self.all.mark(), self.full.mark(), self.clock()

    def since(self, mark: _GcMark) -> Dict[str, float]:
        """A record's `gc_*` fields for the interval that began at `mark`."""
        _n, pause, longest, began = self.all.since(mark[0], mark[2])
        return {"gc_full_collections": self.full.count - mark[1][0],
                "gc_pause_s": round(pause, 6),
                "gc_max_pause_s": round(longest, 6),
                "gc_max_pause_at": round(began, 6) if longest else None}

    def children(self, mark: _GcMark) -> Dict[str, List[float]]:
        """The same interval in a Trace's `children()` form, to graft below
        the stage it covers: `gc` (every generation) and `gc/full`, each
        `[collections, pause_s, the longest]`; neither where none ran."""
        out = {}
        for path, pauses, stood in (("gc", self.all, mark[0]),
                                    ("gc/full", self.full, mark[1])):
            n, pause, longest, _began = pauses.since(stood, mark[2])
            if n:
                out[path] = [n, pause, longest]
        return out


class _GcSeries(Counter):
    """A counter by `generation` that IS one of `GcAccount`'s lists (the
    hook may take no lock, so it keeps no metric): read when it is read."""

    def __init__(self, name: str, help_: str, per_generation: list) -> None:
        super().__init__(name, help_, ("generation",))
        self._per_generation = per_generation

    def value(self, **labels) -> float:
        return float(self._per_generation[int(labels["generation"])])

    def total(self) -> float:
        return float(sum(self._per_generation))

    def expose(self) -> List[str]:
        return self._header() + [
            f"{self.name}{self._fmt_labels(self.label_names, (str(gen),))} "
            f"{float(v)}" for gen, v in enumerate(self._per_generation)]


_GC: Optional[GcAccount] = None
_GC_MU = threading.Lock()


def gc_account() -> GcAccount:
    """The process's `GcAccount`, its hook installed by the first caller
    (the first enabled `SchedulerTelemetry`: `KTPU_TELEMETRY=0` installs
    none) with its two series, `process_gc_collections_total` and
    `process_gc_pause_seconds_total`, by `generation`."""
    global _GC
    with _GC_MU:
        if _GC is None:
            acct = GcAccount()
            DEFAULT_REGISTRY.register(_GcSeries(
                "process_gc_collections_total",
                "Collections of the interpreter's cyclic collector, by "
                "generation", acct.collections))
            DEFAULT_REGISTRY.register(_GcSeries(
                "process_gc_pause_seconds_total",
                "Seconds every thread stood still for the interpreter's "
                "cyclic collector, by generation", acct.pause_s))
            gc.callbacks.append(acct)
            _GC = acct
        return _GC


class SchedulerTelemetry:
    """The scheduler-wide observability layer: one per Scheduler (and one
    per FleetServer). Thread-aware: supervisor events and the device-time
    split arrive from watchdog worker threads; everything else runs on the
    serving loop."""

    def __init__(self, name: str = "scheduler", capacity: Optional[int] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 enabled: Optional[bool] = None,
                 slow_wave_threshold: float = 30.0) -> None:
        if enabled is None:
            enabled = os.environ.get("KTPU_TELEMETRY", "1") not in ("0", "off")
        if capacity is None:
            # KTPU_FLIGHT_RING: ring size, bounds-checked (explicit ctor
            # capacities — tests — win over the env)
            capacity = flight_ring_capacity()
        self.name = name
        self.enabled = enabled
        self.clock = clock
        self.slow_wave_threshold = slow_wave_threshold
        self.tracker = PodLatencyTracker()
        self.recorder = FlightRecorder(capacity)
        # exact-quantile reservoir beside the Prometheus histogram: the
        # latency bench and tests read precise p50/p99 from here while
        # dashboards use histogram_quantile on the exposed buckets
        self.latency_samples: deque = deque(maxlen=8192)
        self._mu = threading.Lock()
        self._pending_events: List[Tuple[str, str]] = []
        # token (wave span) → readings; see note_device_split. Keyed by
        # the token OBJECT (strong ref — an id() key could be reused by a
        # GC'd span), bounded below so abandoned waves' entries can't leak
        self._device_split: Dict[object, Dict[str, float]] = {}
        # the server loop's account of the time since the last wave that
        # attempted pods (loop_reset/loop_lap; None until a loop starts),
        # and the informer handlers' [calls, wait_s, held_s] on the
        # server's lock over the same interval — both drained onto the
        # next such wave's record
        self._loop: Optional[Trace] = None
        self._loop_lap_t = self._loop_stage_t = 0.0
        self._handlers: List[float] = [0, 0.0, 0.0]
        self._synced: Dict[str, bool] = {}
        self._noted: Dict[str, Any] = {}
        # what the loop decided before the wave to come (note_gather)
        self._gather: Optional[Tuple[float, float]] = None
        # the collector's account, and where this recorder's previous
        # record (and the loop account's previous stage) ended on it; None
        # with telemetry off: no hook
        self._gc = gc_account() if enabled else None
        self._gc_mark = self._stage_gc_mark = \
            self._gc.mark() if enabled else None
        self.last_dump: Optional[Dict[str, Any]] = None
        self.dumps = 0
        # KTPU_PROFILE=<dir>: jax.profiler trace capture around dispatches
        self.profile_dir = os.environ.get("KTPU_PROFILE") or None
        self._profiling = False

    # ------------------------------------------------------------------ #
    # tier 1: per-pod e2e latency (watch→bind)
    # ------------------------------------------------------------------ #

    def record_bound(self, key: str, now: float) -> Optional[float]:
        """Binding-commit: close the pod's watch→bind span and feed the
        e2e histogram. `now` must be in the SAME clock domain the queue
        stamped with (the scheduler's injected clock)."""
        if not self.enabled:
            return None
        lat = self.tracker.pop_latency(key, now)
        if lat is None:
            return None
        POD_E2E_LATENCY.observe(lat)
        with self._mu:
            # under _mu: the debug endpoint's quantile read iterates the
            # deque from the gateway thread, and a concurrent append would
            # raise "deque mutated during iteration"
            self.latency_samples.append(lat)
        return lat

    def record_bound_many(self, keys, now: float) -> int:
        """Batch `record_bound` for one wave's commit loop: one tracker
        lock, one histogram lock, one reservoir lock for the whole batch —
        ~3× cheaper per pod than the scalar path, which at 2.7 µs/call was
        most of the measured telemetry overhead on a 2500-pod wave. Same
        clock-domain contract as `record_bound`; returns how many spans
        actually closed."""
        if not self.enabled or not keys:
            return 0
        lats = self.tracker.pop_latencies(keys, now)
        if not lats:
            return 0
        POD_E2E_LATENCY.observe_many(lats)
        with self._mu:
            self.latency_samples.extend(lats)
        return len(lats)

    def latency_quantiles(self, qs=(0.5, 0.99)) -> Dict[float, float]:
        """Exact quantiles (seconds) over the bounded sample reservoir."""
        with self._mu:
            samples = sorted(self.latency_samples)
        if not samples:
            return {q: 0.0 for q in qs}
        return {q: samples[min(int(q * len(samples)), len(samples) - 1)]
                for q in qs}

    # ------------------------------------------------------------------ #
    # tier 2: wave spans + flight recorder
    # ------------------------------------------------------------------ #

    def wave_span(self, name: str = "wave"):
        if not self.enabled:
            return _NULL_SPAN
        return _WaveSpan(self.clock, name, self.slow_wave_threshold)

    def has_pending_events(self) -> bool:
        with self._mu:
            return bool(self._pending_events)

    def note_supervisor_event(self, kind: str, detail: str = "") -> None:
        """sched/supervisor.py `event_sink`: called from the serving loop
        AND from watchdog/prober threads — events accumulate until the
        current wave's `finish_wave` drains them onto its record."""
        if not self.enabled:
            return
        with self._mu:
            self._pending_events.append((kind, str(detail)[:200]))

    def loop_reset(self) -> None:
        """The server starts: its loop's account of the time between waves
        begins here (sched/server.py laps it; a Scheduler driven without
        a server loop never calls this and its records carry no `loop`)."""
        if not self.enabled:
            return
        with self._mu:
            self._new_loop(self.clock())
            self._gc_mark = self._gc.mark()

    def _new_loop(self, t: float) -> None:
        self._loop = Trace("loop", clock=lambda: t)  # its start IS t
        self._loop_lap_t = self._loop_stage_t = t
        self._handlers = [0, 0.0, 0.0]
        self._synced = {}
        self._noted = {}
        self._stage_gc_mark = self._gc.mark()

    def loop_lap(self, name: str) -> None:
        """The server loop (one thread) closes the stretch since its last
        lap — or since the last wave that attempted pods ended — under
        `name`. Laps are contiguous, so the phases on a wave's `loop` sum
        to the gap between the previous wave's end and its own start."""
        loop = self._loop
        if loop is None:
            return
        now = self.clock()
        loop.child(name, now - self._loop_lap_t)
        self._loop_lap_t = self._loop_stage_t = now

    def loop_stage(self, path: str,
                   below: Optional[Dict[str, List[float]]] = None,
                   synced: Optional[bool] = None) -> None:
        """A stage INSIDE the lap that will close over it, on the thread
        that runs it (a server's `start()`): the stretch since the previous
        stage, or the lap before it, is filed under `path`
        (`start/pods-sync` below the `start` lap), on `loop.children`.
        `below` is the `children()` of a Trace that was current while the
        stage's work ran, on whatever thread (an informer's
        `last_sync["children"]`): grafted under `path`. `synced` is the
        verdict of a stage that waited for an informer (`loop.synced`).
        The collections that ended inside the stage are filed below it as
        `gc` (every generation) and `gc/full`: pauses that the spans beside
        them HOLD, not a further share of the stage."""
        loop = self._loop
        if loop is None:
            return
        now = self.clock()
        loop.child(path, now - self._loop_stage_t)
        self._loop_stage_t = now
        if below:
            loop.graft(path, below)
        if synced is not None:
            self._synced[path] = synced
        loop.graft(path, self._gc.children(self._stage_gc_mark))
        self._stage_gc_mark = self._gc.mark()

    def loop_span(self, path: str, seconds: float) -> None:
        """A stage that HOLDS stages already filed below it
        (`start/volumes-sync` over its four lists): its own seconds, without
        moving the mark the next stage is reckoned from."""
        if self._loop is not None:
            self._loop.child(path, seconds)

    def loop_note(self, **fields: Any) -> None:
        """Plain fields of the open account, beside its phases on the
        record that closes it (`loop.start_frozen_objects`: what a server's
        start says of itself that is no stretch of time)."""
        if self._loop is not None:
            self._noted.update(fields)

    def loop_account(self) -> Dict[str, List[float]]:
        """The open account as it stands, `{path: [count, total_s, max_s]}`:
        laps at the top, stages below them."""
        loop = self._loop
        return loop.children() if loop is not None else {}

    def note_handler(self, wait_s: float, held_s: float) -> None:
        """One informer handler call on the server's lock (any thread)."""
        with self._mu:
            h = self._handlers
            h[0] += 1
            h[1] += wait_s
            h[2] += held_s

    def note_gather(self, age_s: float, wait_s: float) -> None:
        """The server loop's decision before a wave (its one thread): how
        long the oldest pod in the active queue had been at the scheduler
        when the loop looked, and what the loop then slept for pods to
        gather (0.0: none). On the next record of a wave that attempted
        pods, as `gather_age_s` and `gather_wait_s`."""
        if self.enabled:
            self._gather = (age_s, wait_s)

    def note_device_split(self, launch: float, execute: float,
                          readback: float, token: object = None) -> None:
        """Tier 3 readings from the dispatch worker: XLA launch vs device
        execution vs host readback for the wave in flight. `token` is the
        wave's span: a watchdog-ABANDONED primary's zombie thread may
        finish minutes later and report its timings — keyed to its own
        (long-finished) span they can neither attach to a later wave's
        record nor clobber that wave's own pending reading."""
        if not self.enabled:
            return
        with self._mu:
            if len(self._device_split) >= 8:
                # stale entries from abandoned waves whose spans never
                # finished — drop them all rather than leak
                self._device_split.clear()
            self._device_split[token] = {
                "launch_s": round(launch, 6),
                "execute_s": round(execute, 6),
                "readback_s": round(readback, 6),
            }

    def finish_wave(self, span, *, stats=None, engine: str = "",
                    dims=None, micro: bool = False,
                    fleet: Optional[Dict[str, Any]] = None,
                    extra: Optional[Dict[str, Any]] = None) -> Optional[Dict]:
        """Close one wave: derive phase durations, feed the per-phase
        histograms, attach drained supervisor events + device split, ring
        the record, and auto-dump when a trigger event is present."""
        if not self.enabled or not getattr(span, "enabled", False):
            return None
        phases = span.phases()
        for phase, dt in phases:
            SCHEDULING_DURATION.observe(dt, operation=phase)
        t_end = self.clock()
        loop_rec = gather = None
        with self._mu:
            attempted = stats is not None and stats.attempted
            if attempted:
                gather, self._gather = self._gather, None
            if self._loop is not None and attempted:
                # what the server loop did since the last wave like this
                # one; its account starts over where this wave ends
                calls, wait_s, held_s = self._handlers
                account = self._loop.record()
                if account or calls:   # a loop nobody lapped has none
                    loop_rec = {
                        "t_start": round(self._loop.start, 6),
                        "phases": [[n, v[1]] for n, v in account.items()
                                   if "/" not in n],
                        "handlers": {"calls": calls,
                                     "wait_s": round(wait_s, 6),
                                     "held_s": round(held_s, 6)}}
                    below = {p: v for p, v in account.items() if "/" in p}
                    if below:
                        loop_rec["children"] = below
                    if self._synced:
                        loop_rec["synced"] = self._synced
                    loop_rec.update(self._noted)
                self._new_loop(t_end)
            events, self._pending_events = self._pending_events, []
            # this wave's own reading (or an untokened caller's); entries
            # keyed to OTHER spans are abandoned waves' zombie reports —
            # left behind and bounded-cleared by note_device_split
            split = self._device_split.pop(span, None) \
                or self._device_split.pop(None, None)
            # what the collector did since the previous record ended
            gc_fields = self._gc.since(self._gc_mark)
            self._gc_mark = self._gc.mark()
        rec: Dict[str, Any] = {
            "recorder": self.name,
            "t_start": round(span.trace.start, 6),
            "duration_s": round(t_end - span.trace.start, 6),
            "phases": [(p, round(dt, 6)) for p, dt in phases],
            "engine": engine,
        }
        if micro:
            # micro-waves (ISSUE 18) are first-class flight-recorder
            # citizens: the flag lets an incident reader separate the
            # streaming admissions from the bulk cadence at a glance
            rec["micro"] = True
        if dims is not None:
            rec["bucket"] = {"N": dims.N, "P": dims.P, "E": dims.E,
                             "D": dims.D}
            # which parameterisation of the pod-affinity aggregate this
            # record's compiled program runs (state/dims.py affinity_agg)
            agg = dims.affinity_agg(engine)
            if agg is not None:
                rec["affinity_agg"] = agg
            # and how that program sums a table over topology domains
            # (state/dims.py domain_sum)
            form = dims.domain_sum(engine)
            if form is not None:
                rec["domain_sum"] = form
        if stats is not None:
            rec["stats"] = {
                "attempted": stats.attempted,
                "scheduled": stats.scheduled,
                "unschedulable": stats.unschedulable,
                "bind_errors": stats.bind_errors,
                "aborted": stats.aborted,
                "requeued": getattr(stats, "requeued", 0),
                "degraded": getattr(stats, "degraded", 0),
                "shed": getattr(stats, "shed", 0),
            }
        if events:
            rec["supervisor_events"] = events
        if split is not None:
            rec["device_split"] = split
        children = span.trace.record()
        if children:
            rec["children"] = children
        if loop_rec is not None:
            rec["loop"] = loop_rec
        if gather is not None:
            rec["gather_age_s"] = round(gather[0], 6)
            rec["gather_wait_s"] = round(gather[1], 6)
        if fleet is not None:
            rec["fleet"] = fleet
        rec.update(gc_fields)
        if extra:
            rec.update(extra)
        self.recorder.record(rec)
        span.trace.log_if_long(self.slow_wave_threshold)
        present = {k for k, _ in events}
        trigger = next((t for t in DUMP_TRIGGERS if t in present), None)
        if trigger is not None:
            self.dump(trigger)
        return rec

    def snapshot_doc(self, trigger: str) -> Dict[str, Any]:
        """The dump DOCUMENT without the dump SIDE EFFECTS — what a
        read-only consumer (the /debug/flightrecorder endpoint) serves. A
        scrape loop must neither clobber `last_dump` (the incident
        artifact an auto-dump left behind), count as a dump, nor write
        KTPU_FLIGHT_DIR files."""
        doc = self.recorder.snapshot(trigger)
        doc["recorder"] = self.name
        q = self.latency_quantiles()
        doc["latency_p50_s"] = round(q[0.5], 6)
        doc["latency_p99_s"] = round(q[0.99], 6)
        return doc

    def dump(self, trigger: str, path: Optional[str] = None) -> Dict[str, Any]:
        """Snapshot the ring into one structured-JSON document. Written to
        `path` when given, else to KTPU_FLIGHT_DIR (one file per dump) when
        set; always retained as `last_dump` and counted per trigger.
        Side-effect-free while disabled: KTPU_TELEMETRY=0 must not let an
        unconditional call site (the takeover pass) clobber a prior
        incident artifact with an empty-ring document."""
        doc = self.snapshot_doc(trigger)
        if not self.enabled:
            return doc
        self.last_dump = doc
        self.dumps += 1
        FLIGHT_DUMPS.inc(trigger=trigger)
        if path is None:
            flight_dir = os.environ.get("KTPU_FLIGHT_DIR")
            if flight_dir:
                # KTPU_FLIGHT_GZIP: gzip auto-dumped artifacts (the bloat
                # knob for long soak runs; explicit `path` callers opt in
                # by passing a .gz path themselves)
                suffix = ".json.gz" if os.environ.get(
                    "KTPU_FLIGHT_GZIP", "") not in ("", "0") else ".json"
                path = os.path.join(
                    flight_dir,
                    f"flight-{self.name}-{trigger}-{doc['last_seq']}{suffix}")
        if path:
            try:
                _write_dump(doc, path)
            except OSError:
                pass  # a full disk must never take down the serving loop
        return doc

    # ------------------------------------------------------------------ #
    # tier 3: device-time profiling (KTPU_PROFILE)
    # ------------------------------------------------------------------ #

    def device_annotation(self, name: str):
        """Context for the primary dispatch: a jax.profiler TraceAnnotation
        when KTPU_PROFILE is set (starting the profiler trace lazily on
        first use), else a null context. Never raises."""
        import contextlib

        if not self.enabled or self.profile_dir is None:
            return contextlib.nullcontext()
        try:
            import jax

            if not self._profiling:
                self._profiling = True
                jax.profiler.start_trace(self.profile_dir)
            return jax.profiler.TraceAnnotation(name)
        except Exception:  # noqa: BLE001 - profiling must never break a wave
            return contextlib.nullcontext()

    def stop_profile(self) -> None:
        if not self._profiling:
            return
        self._profiling = False
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 - shutdown must never raise
            pass
