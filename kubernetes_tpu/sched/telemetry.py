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
`gc_max_pause_s`, `gc_max_pause_at` (`GcAccount`, one hook a process), and
what XLA cost the process: `xla_total` (programs compiled or loaded, cache
misses, backend and trace+lower seconds since the hook went in) and, where
any ended since the record before, `xla_compiled`: each trace, lowering,
cache verdict and compile folded into one entry with who asked, at what
signature and what set it apart (`XlaAccount`, one listener pair a process;
ISSUE 51).

Kill switch: ``KTPU_TELEMETRY=0`` turns every tier into a no-op (the
`latency` bench stage uses it to bound telemetry overhead at <2% of the
untelemetered flagship pods/s).
"""

from __future__ import annotations

import dataclasses
import gc
import gzip
import json
import logging
import os
import re
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..component.metrics import DEFAULT_REGISTRY, Counter
from ..component.trace import Trace
from .metrics import (FLIGHT_DUMPS, POD_E2E_LATENCY, SCHEDULING_DURATION,
                      XLA_PROGRAMS, XLA_SECONDS)

logger = logging.getLogger("kubernetes_tpu.sched.telemetry")

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

    def next_seq(self) -> int:
        """The `seq` the next record will get: the wave in flight's."""
        return self._seq + 1

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


# --------------------------------------------------------------------- #
# the XLA account (ISSUE 51): which program, for whom, and why
# --------------------------------------------------------------------- #

#: jax.monitoring's names (jax 0.9.0), in the order one program fires them
#: on the thread that compiles it
_XLA_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_XLA_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_XLA_BACKEND = "/jax/core/compile/backend_compile_duration"
_XLA_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_XLA_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_XLA_VERDICT = {"/jax/compilation_cache/cache_hits": "hit",
                "/jax/compilation_cache/cache_misses": "miss"}
#: an on-path entry at least this long is a WARNING on the log
XLA_WARN_S = 1.0
_JIT_OF = re.compile(r"^\w+\((.*)\)$")   # "jit(f)" -> "f"


class _XlaScope:
    """Who asks, on this thread, from `__enter__` to `__exit__`: what an
    entry that ends inside is filed under. One thread-local store on the
    way in, one restore on the way out; nothing else runs unless jax
    compiles."""

    __slots__ = ("account", "stage", "sig", "names", "on_path", "cold",
                 "seq", "sink", "_outer")

    def __init__(self, account, stage, sig, names, on_path, cold, seq, sink):
        self.account = account
        self.stage, self.sig, self.names = stage, sig, names
        self.on_path, self.cold, self.seq, self.sink = \
            on_path, cold, seq, sink

    def __enter__(self):
        local = self.account._local
        outer = self._outer = getattr(local, "scope", None)
        if outer is not None:
            # what this scope leaves open is the enclosing one's: a
            # narrower scope names the key, its caller who waits
            for field in ("stage", "on_path", "cold", "seq", "sink"):
                if getattr(self, field) is None:
                    setattr(self, field, getattr(outer, field))
        local.scope = self
        return self

    def __exit__(self, *exc):
        self.account._local.scope = self._outer
        return False


def _plain(v):
    """A signature's value as JSON keeps it."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (tuple, list)):
        return [_plain(x) for x in v]
    return str(v)[:120]


def _sig_fields(sig, names) -> Optional[Dict[str, Any]]:
    """A scope's key by field: a dataclass among its parts (the `Dims`) by
    its own field names, the others under `names` (positions where the
    names do not fit the key)."""
    if sig is None:
        return None
    if isinstance(sig, dict):
        items = list(sig.items())
    else:
        parts = sig if isinstance(sig, tuple) else (sig,)
        if len(names) != len(parts):
            names = tuple(f"k{i}" for i in range(len(parts)))
        items = list(zip(names, parts))
    out: Dict[str, Any] = {}
    for name, v in items:
        if dataclasses.is_dataclass(v) and not isinstance(v, type):
            for f in dataclasses.fields(v):
                out[f.name] = _plain(getattr(v, f.name))
        else:
            out[name] = _plain(v)
    return out


class XlaAccount:
    """What XLA cost this process, a program at a time: every trace,
    lowering, persistent-cache verdict and backend compile (or load) that
    jax reports, folded per thread into ONE entry a program, with the scope
    it ran under. ONE `jax.monitoring` listener pair a process
    (`xla_account()`), called by jax on the compiling thread and only when
    it traces or compiles: nothing here runs on a wave that compiles
    nothing.

    An entry: `fun`, `stage` (None: a site nobody wrapped), `sig`,
    `differs` (`{field: [nearest, this]}` against the nearest earlier
    signature of the same `fun` and `stage`; None for the first), `cache`
    (`hit` / `miss` / `unstored`: asked, not found, and compiled in under
    `jax_persistent_cache_min_compile_time_secs`, so never stored / `off`:
    jax asked no cache),
    `trace_s`, `lower_s`, `backend_s`, `saved_s`, `cold`, `on_path`,
    `thread`, `t_start` / `t_end` on `clock`, `seq`. The newest `KEEP`
    entries are kept, and four totals since the hook went in."""

    KEEP = 256
    #: earlier signatures remembered a (fun, stage), newest last
    NEAREST = 32

    def __init__(self, clock: Callable[[], float] = time.perf_counter) -> None:
        self.clock = clock
        self._mu = threading.Lock()
        self._local = threading.local()   # .scope, .making
        self._entries: deque = deque(maxlen=self.KEEP)
        self._seen: Dict[Tuple[str, Optional[str]], deque] = {}
        # entries ever finished, one a backend event: `mark()`, and the
        # total `programs`
        self.count = self.cache_misses = 0
        self.backend_s = self.trace_lower_s = 0.0

    # -- who asks -- #

    def scope(self, stage: Optional[str], sig=None, names: tuple = (), *,
              on_path: Optional[bool] = None, cold: Optional[bool] = None,
              seq: Optional[int] = None,
              sink: Optional[Callable[[str, str], None]] = None):
        """A context for the calling thread: entries that end inside it are
        filed under `stage` at `sig` (a key as the caller already builds
        it; `names` for its parts). `on_path`: a started server's dispatch
        waits for this thread; `cold`: the supervisor had no budget for the
        key; `seq`: the record the wave in flight will get; `sink`: where
        an on-path entry is narrated (`note_supervisor_event`). All but
        `sig` default to the enclosing scope's."""
        return _XlaScope(self, stage, sig, names, on_path, cold, seq, sink)

    # -- jax's side: the two listeners -- #

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        fold = self._FOLDS.get(event)
        if fold is None:
            return
        try:
            fold(self, kw.get("fun_name", "?"), seconds)
        except Exception:  # noqa: BLE001 - the account never breaks a compile
            logger.debug("xla account: %s", event, exc_info=True)

    def on_event(self, event: str, **kw) -> None:  # noqa: ARG002
        # asked, and so far neither found nor stored: jax reports a miss
        # where it WRITES the entry, so a compile under the minimum compile
        # time is asked for and never heard of again
        if event == _XLA_ASKED:
            self._making().setdefault("cache", "unstored")
        elif event in _XLA_VERDICT:
            self._making()["cache"] = _XLA_VERDICT[event]

    def _making(self) -> Dict[str, Any]:
        """What this thread has of the program it is making: `traces` (by
        name), the lowering (`fun`, `lower_s`), `cache`, `saved_s`."""
        making = getattr(self._local, "making", None)
        if making is None:
            making = self._local.making = {"traces": {}}
        return making

    def _on_saved(self, _fun: str, seconds: float) -> None:
        self._making()["saved_s"] = seconds

    def _on_trace(self, fun: str, seconds: float) -> None:
        # kept by name until the lowering that names it: a program's trace
        # ends after those of every jit traced inside it, and the lowering
        # itself traces helpers before it reports
        self._making()["traces"][fun] = (seconds, self.clock() - seconds)

    def _on_lower(self, module: str, seconds: float) -> None:
        self._making().update(fun=_JIT_OF.sub(r"\1", module),
                              lower_s=seconds)

    def _on_backend(self, module: str, seconds: float) -> None:
        making, self._local.making = self._making(), None
        fun, lower_s = _JIT_OF.sub(r"\1", module), making.get("lower_s", 0.0)
        if making.get("fun") != fun:
            lower_s = 0.0   # `.compile()` of what another thread lowered
        # no trace by that name: a jaxpr traced earlier, lowered anew
        trace_s, began = making["traces"].get(
            fun, (0.0, self.clock() - seconds - lower_s))
        self._finish(fun, began, trace_s, lower_s, seconds,
                     making.get("cache", "off"), making.get("saved_s"))

    def _finish(self, fun: str, began: float, trace_s: float, lower_s: float,
                backend_s: float, cache: str,
                saved_s: Optional[float]) -> None:
        scope = getattr(self._local, "scope", None)
        stage = scope.stage if scope is not None else None
        sig = _sig_fields(scope.sig, scope.names) if scope is not None \
            else None
        entry = {
            "fun": fun, "stage": stage, "sig": sig,
            "differs": None, "cache": cache,
            "trace_s": round(trace_s, 6),
            "lower_s": round(lower_s, 6),
            "backend_s": round(backend_s, 6),
            "saved_s": None if saved_s is None else round(saved_s, 6),
            "cold": scope.cold if scope is not None else None,
            "on_path": bool(scope is not None and scope.on_path),
            "thread": threading.current_thread().name,
            "t_start": round(began, 6),
            "t_end": round(self.clock(), 6),
            "seq": scope.seq if scope is not None else None,
        }
        with self._mu:
            seen = self._seen.setdefault((fun, stage),
                                         deque(maxlen=self.NEAREST))
            if sig is not None:
                entry["differs"] = _differs(seen, sig)
                seen.append(sig)
            self._entries.append(entry)
            self.count += 1
            self.backend_s += backend_s
            if cache == "miss":
                self.cache_misses += 1
            self.trace_lower_s += trace_s + lower_s
        label = stage or "none"
        XLA_PROGRAMS.inc(stage=label, cache=cache)
        XLA_SECONDS.inc(backend_s, stage=label, part="backend")
        XLA_SECONDS.inc(trace_s, stage=label, part="trace")
        XLA_SECONDS.inc(lower_s, stage=label, part="lower")
        on_path = entry["on_path"]
        loud = on_path and backend_s + trace_s + lower_s > XLA_WARN_S
        if not (on_path or loud or logger.isEnabledFor(logging.INFO)):
            return
        line = describe_compile(entry)
        logger.log(logging.WARNING if loud else logging.INFO, "%s", line)
        if on_path and scope.sink is not None:
            try:
                scope.sink("compile", line)
            except Exception:  # noqa: BLE001 - narration never breaks jax
                pass

    _FOLDS = {_XLA_TRACE: _on_trace, _XLA_LOWER: _on_lower,
              _XLA_BACKEND: _on_backend, _XLA_SAVED: _on_saved}

    # -- the readers' side -- #

    def mark(self) -> int:
        """Where the account stands: what `since` takes."""
        return self.count

    def since(self, mark: int) -> Tuple[Dict[str, Any], int]:
        """A record's `xla_*` fields for the interval that began at `mark`,
        and the mark the next interval begins at: `xla_total` (the totals
        as they stand: process-wide, so an interval is the difference of
        two records) and, where any entry ended since `mark` and is still
        kept, `xla_compiled` (oldest first)."""
        with self._mu:
            fields: Dict[str, Any] = {"xla_total": self._totals()}
            fresh = min(self.count - mark, len(self._entries))
            if fresh > 0:
                fields["xla_compiled"] = \
                    list(self._entries)[len(self._entries) - fresh:]
            return fields, self.count

    def entries(self) -> List[Dict[str, Any]]:
        """The kept entries, oldest first."""
        with self._mu:
            return list(self._entries)

    def totals(self) -> Dict[str, float]:
        """The four running totals since the hook went in: `programs`
        (backend events: compiles and loads), `cache_misses` (programs
        compiled and stored: jax counts a miss where it writes),
        `backend_s`, `trace_lower_s`."""
        with self._mu:
            return self._totals()

    def _totals(self) -> Dict[str, float]:
        return {"programs": self.count,
                "cache_misses": self.cache_misses,
                "backend_s": round(self.backend_s, 6),
                "trace_lower_s": round(self.trace_lower_s, 6)}


def _differs(seen, sig: Dict[str, Any]) -> Optional[Dict[str, List[Any]]]:
    """`{field: [nearest, this]}` against the earlier signature that
    differs from `sig` in the fewest fields (the newest of equals); None
    where there is none."""
    best = None
    for old in reversed(seen):
        diff = {k: [old.get(k), sig.get(k)]
                for k in {**old, **sig} if old.get(k) != sig.get(k)}
        if best is None or len(diff) < len(best):
            best = diff
    return best


def describe_compile(entry: Dict[str, Any]) -> str:
    """One line an entry, for the log and the supervisor event: who waited
    how long for what, the cache's verdict, and what set the signature
    apart from the nearest one this process had."""
    took = entry["backend_s"] + entry["trace_s"] + entry["lower_s"]
    who = f"wave {entry['seq']}" if entry["seq"] is not None \
        else f"thread {entry['thread']}"
    verb = "waited" if entry["on_path"] else "spent"
    differs = entry["differs"]
    if differs is None:
        why = "first signature of this program here"
    elif not differs:
        why = "the signature of an earlier program"
    else:
        why = "differs from the nearest earlier program in " + ", ".join(
            f"{k} {a} -> {b}" for k, (a, b) in sorted(differs.items()))
    cold = {True: ", cold", False: ", warm key"}.get(entry["cold"], "")
    return (f"{who} {verb} {took:.3f} s for `{entry['fun']}` "
            f"(stage {entry['stage']}{cold}): cache {entry['cache']}, "
            f"trace {entry['trace_s']:.3f} lower {entry['lower_s']:.3f} "
            f"backend {entry['backend_s']:.3f}; {why}")


_XLA: Optional[XlaAccount] = None
_XLA_MU = threading.Lock()


def xla_account() -> XlaAccount:
    """The process's `XlaAccount`. Its listener pair goes in with the
    first call that finds telemetry on (`utils/platform.py
    enable_compile_cache`, which every entry point calls before its first
    compile; the first enabled `SchedulerTelemetry` otherwise):
    `KTPU_TELEMETRY=0` installs none, and the account then stays empty and
    its scopes are thread-local stores nobody reads."""
    global _XLA
    if _XLA is not None:   # every scope comes through here
        return _XLA
    with _XLA_MU:
        if _XLA is None:
            _XLA = XlaAccount()
            if os.environ.get("KTPU_TELEMETRY", "1") not in ("0", "off"):
                import jax.monitoring

                jax.monitoring.register_event_duration_secs_listener(
                    _XLA.on_duration)
                jax.monitoring.register_event_listener(_XLA.on_event)
        return _XLA


def xla_scope(stage: str, sig=None, names: tuple = (), **who):
    """`xla_account().scope(...)`: the context a compile site enters round
    the call that may compile, with the key it already builds."""
    return xla_account().scope(stage, sig, names, **who)


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
        # the XLA account, and where this recorder's previous record ended
        # on it (a new recorder's first record carries what the process
        # compiled since it was made; `xla_total` what it paid in all)
        self._xla = xla_account() if enabled else None
        self._xla_mark = self._xla.mark() if enabled else 0
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
            # and what XLA cost the process, with the programs it made
            xla_fields, self._xla_mark = self._xla.since(self._xla_mark)
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
        rec.update(xla_fields)
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
