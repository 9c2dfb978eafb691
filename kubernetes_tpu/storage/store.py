"""storage.Interface: versioned object storage over the MVCC kvstore.

Analog of `staging/src/k8s.io/apiserver/pkg/storage/etcd3/store.go`: objects
are JSON-encoded under `/registry/<resource>/[<ns>/]<name>`; as in the
reference, resourceVersion is NOT stored in the value — it is filled from the
record's mod_revision on every read (store.go Versioner). GuaranteedUpdate
retries a CAS on mod_revision (store.go:219-300); Watch delivers events from
a given revision with 410-Gone on compaction. One dispatcher thread pumps kv
events to all registered watchers (role of etcd watch streams + the apiserver
Cacher, storage/cacher/cacher.go:309).

Who owns an object (ISSUE 28). The store keeps BYTES, never a reference to
an object: what `get` / `list` / `delete` return, what `guaranteed_update`
hands `update_fn` and what it returns are freshly decoded or the caller's
own, so nothing here copies an object. `create(key, obj)` and the object
`update_fn` returns are given UP to the store for the length of the call:
it encodes them, stamps the new resourceVersion on them and returns them.
A watch stream's consumer receives an object of its own, decoded from the
event's bytes as the consumer takes it (`storage/cacher.py`).

Watch-plane contract (ISSUE 13, the cacher's delivery discipline):

  * every watcher owns a BOUNDED buffer (`KTPU_WATCH_BUFFER`, default 8192)
    and its own place in the stream (`_Watcher.since`). The pump hands a
    watcher what its buffer has room for and comes back with the rest:
    from the cacher ring, or beneath the ring's horizon from the KV
    history. A consumer that is slow, or blocked for a while (a scheduler's
    informer behind a long wave), is delivered LATE, never less, and is
    never made to relist; the broadcast never blocks or balloons for it;
  * a consumer whose buffer stays full for `DEAF_AFTER_S` is deaf and is
    cut off (cacher.go forgetWatcher): a terminal 504 Status it can RESUME
    from by resourceVersion while the events it still needs exist, a 410
    "too old resource version" (relist) only when compaction has taken
    them — the one real gap;
  * BOOKMARK events carry the dispatched revision on a timer AND immediately
    on every compaction-boundary crossing (`compact_to`), so a quiet
    stream's resume token stays above the compaction floor and reconnects
    resume instead of relisting;
  * `drop_watchers` (the apiserver-restart seam) emits a terminal 503
    Status BEFORE closing each stream — clients resume by resourceVersion
    rather than discovering death by socket EOF and blind-relisting;
  * the pump's fixed cost is paid per TURN, and a turn is many events long
    while writes stream in (ISSUE 28): a write on a quiet store is
    broadcast at once; a turn that follows the previous one within
    `GATHER_S` found a writer at work and, unless `GATHER_EVENTS` wait
    already, sleeps `GATHER_S` to let the log gather first — off the
    interpreter the writer needs.
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from kubernetes_tpu.component import trace
from kubernetes_tpu.component.metrics import DEFAULT_REGISTRY as _REG
from kubernetes_tpu.machinery import errors, meta
from kubernetes_tpu.machinery import watch as mwatch
from kubernetes_tpu.storage import native
from kubernetes_tpu.storage.cacher import CachedEvent, WatchCache
from kubernetes_tpu.storage.cacher import decode as _decode
from kubernetes_tpu.utils import faultline

Obj = Dict[str, Any]
Predicate = Optional[Callable[[Obj], bool]]

# watch-plane delivery telemetry (ISSUE 13): the per-watcher buffer is the
# backpressure boundary — its depth is the early-warning signal, and an
# eviction is the cacher contract actually firing (one deaf consumer paid,
# everyone else's broadcast stayed live)
WATCH_BUFFER_DEPTH = _REG.gauge(
    "watch_buffer_depth",
    "Deepest per-watcher delivery buffer observed at dispatch, by resource",
    labels=("resource",))
WATCH_DEAF_EVICTIONS = _REG.counter(
    "apiserver_watch_deaf_evictions_total",
    "Watch streams cut off because the consumer left its bounded buffer "
    "full for DEAF_AFTER_S (cacher forgetWatcher contract)",
    labels=("resource",))
WATCH_PUMP_LAG = _REG.gauge(
    "storage_watch_pump_lag_events",
    "Store head revision less the revision the watch pump had broadcast, "
    "read at each turn of the pump: writes no watcher has been offered yet")
WATCH_PUMP_BATCH_MAX = _REG.gauge(
    "storage_watch_pump_batch_max_events",
    "Largest number of events one turn of the watch pump broadcast")
# what the broadcast costs and how well it batches: the pump thread's own CPU
# seconds, its turns that broadcast, and the events they carried (events per
# turn is how often the gathering engages)
WATCH_PUMP_BUSY = _REG.counter(
    "storage_watch_pump_busy_seconds_total",
    "CPU seconds of the watch pump's own thread (time.thread_time), "
    "read once per turn")
WATCH_PUMP_TURNS = _REG.counter(
    "storage_watch_pump_turns_total",
    "Turns of the watch pump that broadcast at least one event")
WATCH_PUMP_EVENTS = _REG.counter(
    "storage_watch_pump_events_total",
    "Events the watch pump broadcast")
WATCH_BOOKMARKS_SENT = _REG.counter(
    "apiserver_watch_bookmarks_sent_total",
    "BOOKMARK events sent to opted-in watchers, by trigger "
    "(timer, compaction)",
    labels=("trigger",))
# one write through the store (the etcd3 store's
# `etcd_request_duration_seconds` seat): read, transform, encode, CAS put,
# retries included. The watch fan-out runs on the dispatch thread, outside.
# `list` is the one read counted: the range scan and the decode of every
# record it returned (an informer's initial list is a server's start).
TXN_DURATION = _REG.histogram(
    "storage_txn_duration_seconds",
    "One write transaction through the store (or one list), by operation",
    labels=("op",),
    buckets=(0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
             0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5))
_OP_CREATE, _OP_UPDATE, _OP_DELETE = ("create",), ("update",), ("delete",)
_OP_LIST = ("list",)
_EVENT_TYPES = {native.EVENT_CREATE: mwatch.ADDED,
                native.EVENT_PUT: mwatch.MODIFIED,
                native.EVENT_DELETE: mwatch.DELETED}
#: a watcher whose buffer has been full this long, with events waiting, is
#: deaf. Waiting costs the pump nothing (it never blocks on a watcher, and a
#: lagging one costs a bounded read a turn), so the budget is generous: a
#: consumer that is alive but kept from reading for tens of seconds (an
#: informer whose handler waits on its owner's lock) is not cut off.
DEAF_AFTER_S = 60.0
#: the longest one write waits for the watch pump (`Storage._pace`)
PACE_WAIT_S = 0.1
#: a turn of the pump that starts less than this long after the previous one
#: ended found a stream of writes; unless GATHER_EVENTS events wait already,
#: it lets the log gather this long before it reads. A watcher under a
#: stream of writes hears of one this much later, in exchange for a pump that
#: costs the writer's interpreter a fixed price per turn and not per event.
GATHER_S = 0.005
GATHER_EVENTS = 64
#: the buffer-depth gauges are scraped, not awaited: exported by quiet turns
#: and, while events flow, no more often than this
EXPORT_EVERY_S = 0.1


def _txn_done(op: Tuple[str], t0: float, kv_s: float = 0.0,
              paced_s: float = 0.0, span: str = "store.txn") -> None:
    """Close one transaction: the histogram, and — when the caller's
    thread runs a traced operation (a scheduling wave, an informer's
    list+replace round) — a `store.txn` child of the span that caused it
    (`store.list` for a list), with the seconds of it spent inside the KV
    backend's calls as `<span>/kv` and those it waited for the watch pump
    as `<span>/pace` (the rest is this module's Python: decode, the
    caller's transform or predicate, encode)."""
    dt = time.perf_counter() - t0
    TXN_DURATION.observe_at(op, dt)
    tr = trace.current()
    if tr is not None:
        tr.child(span, dt)
        if kv_s:
            tr.child(span + "/kv", kv_s)
        if paced_s:
            tr.child(span + "/pace", paced_s)


def _parse_watch_buffer(value, default: int = 8192) -> int:
    """Bounds-checked buffer parse (the KTPU_FLIGHT_RING convention):
    garbage falls back to the default, and the result clamps to [1, 2^20]
    — 0/negative would make queue.Queue UNBOUNDED, silently disabling the
    deaf-eviction contract this buffer exists to enforce."""
    try:
        n = int(value)
    except (TypeError, ValueError):
        return default
    return max(1, min(n, 1 << 20))


# json.dumps with these arguments builds this encoder anew on every call
_dumps = json.JSONEncoder(separators=(",", ":"), sort_keys=True).encode


def _encode(obj: Obj) -> bytes:
    obj = dict(obj)
    md = dict(obj.get("metadata") or {})
    md.pop("resourceVersion", None)
    obj["metadata"] = md
    return _dumps(obj).encode()


def _resource_of(prefix: str) -> str:
    """`/registry/<group>/<resource>/…` → `<resource>` (metric label
    granularity; registry.Store.key_root shape — `/registry/core/pods/` →
    `pods`). Bare test prefixes like `/registry/pods/` fall back to their
    last segment."""
    parts = prefix.strip("/").split("/")
    if len(parts) >= 3:
        return parts[2]
    return parts[-1] if parts and parts[-1] else "all"


@dataclass
class _Watcher:
    """One registered watch stream: the delivery buffer plus its horizon.

    `since` is this watcher's place in the stream: every event at/below it
    has been handed over (or filtered out) and must never be re-delivered.
    A watcher whose `since` is behind the pump's dispatched revision LAGS;
    `full_since` is the instant its buffer was first found full since it
    last made room (0.0: it has room); `counted` says the watch cache has
    counted the catch-up under way as a hit or a storage fallback (once an
    episode, however many refills it takes). `bookmarks` opts the stream into
    BOOKMARK events (allowWatchBookmarks)."""

    prefix: str
    watch: mwatch.Watch
    predicate: Predicate
    since: int
    bookmarks: bool
    resource: str = field(default="")
    full_since: float = 0.0
    counted: bool = False  # this lagging episode is on the cache's counters

    def __post_init__(self):
        if not self.resource:
            self.resource = _resource_of(self.prefix)


def _too_old_status(detail: str) -> Obj:
    return errors.new_gone(f"too old resource version: {detail}").status()


class Storage:
    """Object store + watch hub over one KV backend."""

    def __init__(self, kv=None, watch_buffer: Optional[int] = None,
                 bookmark_interval: Optional[float] = None,
                 data_dir: Optional[str] = None,
                 durability: Optional[str] = None):
        # data_dir turns the store durable: the kv is wrapped in the
        # WAL/snapshot layer (storage/wal.py) and recovery has ALREADY run
        # by the time new_kv returns — self.kv.rev() below is the last
        # durable revision, so the pump, the cacher horizon and every RV
        # this process hands out continue the pre-crash sequence
        self.kv = kv if kv is not None else native.new_kv(
            data_dir=data_dir, durability=durability)
        self._watch_mu = threading.Lock()
        self._watchers: List[_Watcher] = []
        self._watch_buffer = _parse_watch_buffer(
            watch_buffer if watch_buffer is not None
            else os.environ.get("KTPU_WATCH_BUFFER"))
        self._bookmark_interval = float(
            bookmark_interval if bookmark_interval is not None
            else os.environ.get("KTPU_WATCH_BOOKMARK_INTERVAL", "10"))
        self._dispatched_rev = self.kv.rev()
        # Cacher tier (storage/cacher.py ⇔ cacher.go:309): the pump puts
        # each event once into this ring; watcher catch-up replays from it so
        # storage reads stay independent of watcher count
        self.watch_cache = WatchCache(horizon=self._dispatched_rev)
        # resources the depth gauge was last exported for: when a
        # resource's final watcher stops, its series must drop to 0 rather
        # than freeze at the last (typically full-buffer) reading
        self._depth_resources: set = set()
        # watch-plane counters the bench/chaos drills assert against
        self.deaf_evictions = 0
        self.deaf_after_s = DEAF_AFTER_S
        # the pump's lag (store head less dispatched revision) as each
        # reader's own high-water mark (`watch_plane_reader`), and the
        # largest batch one turn broadcast
        self._lag_marks: Tuple[List[int], ...] = ()
        self.pump_batch_max = 0
        # the pump's own CPU seconds, its turns that broadcast and the
        # events they carried, written by the pump once per turn
        self.pump_busy_s = 0.0
        self.pump_turns = 0
        self.pump_events = 0
        self._lagging = False  # some live watcher is behind the pump
        # flow control at the source (`_pace`): a write that finds the pump
        # more than `_pace_high` events behind waits for it to come within
        # half of that. An eighth of a watcher's buffer: the pump never
        # comes back to a level watcher with more than its buffer holds.
        self._pace_high = max(self._watch_buffer // 8, 1)
        self._pace_cv = threading.Condition()
        self._pace_waiting = 0
        self._pace_stuck_at = -1  # pump revision a whole wait saw unmoved
        self.paced_writes = 0
        self.bookmarks_sent = 0
        self.compaction_bookmarks = 0
        self._stop = threading.Event()
        self._pump = threading.Thread(target=self._dispatch_loop,
                                      name="storage-watch-pump", daemon=True)
        self._pump.start()

    def close(self) -> None:
        self._stop.set()
        self._pump.join(timeout=2)
        with self._watch_mu:
            for wr in self._watchers:
                wr.watch.stop()
            self._watchers.clear()
        self.kv.close()

    def drop_watchers(self) -> int:
        """Terminate every registered watch stream (the data survives).
        This is what an apiserver restart looks like from a client: the
        store (etcd) keeps its state, every open watch connection dies, and
        reflectors re-establish. Each stream gets a terminal 503 Status
        FIRST (the reference closes the response with a Status frame), so
        informers resume from their last resourceVersion instead of
        discovering death by socket EOF and falling into the blind-relist
        path. Used by the chaos injector's ``apiserver.restart`` seam;
        returns the number of streams dropped."""
        status = errors.new_service_unavailable(
            "apiserver restarting; watch stream closed").status()
        with self._watch_mu:
            n = len(self._watchers)
            for wr in self._watchers:
                wr.watch.terminate(mwatch.Event(mwatch.ERROR, status))
            self._watchers.clear()
        return n

    @property
    def dispatched_rev(self) -> int:
        """How far the broadcast pump has gotten. A compaction drill that
        wants to move the floor WITHOUT manufacturing a pump gap (events
        destroyed before they were ever broadcast 410 every live watcher)
        compacts at this revision, not the kv head."""
        return self._dispatched_rev

    def _pace(self, rev: int) -> float:
        """Flow control at the source, after a write landed at `rev`: a
        writer that outruns the watch pump waits, at most PACE_WAIT_S, until
        the pump is within half of `_pace_high` events of it. A loop of
        short writes on one interpreter (a 30,000-Binding wave) otherwise
        starves the pump for as long as it runs — each write's native call
        frees the interpreter for microseconds and the writer has it back
        before the pump wakes — and every watcher sees the whole wave
        seconds late, in one burst. Writers wait only for a pump that
        moves: one that is dead, or that a whole wait saw stand still,
        costs them nothing more until it moves again. Returns the seconds
        waited."""
        at = self._dispatched_rev
        if rev - at <= self._pace_high or at == self._pace_stuck_at \
                or not self._pump.is_alive():
            return 0.0
        t0 = time.perf_counter()
        low = self._pace_high // 2
        with self._pace_cv:
            self._pace_waiting += 1
            self._pace_cv.wait_for(
                lambda: rev - self._dispatched_rev <= low
                or self._stop.is_set(), timeout=PACE_WAIT_S)
            self._pace_waiting -= 1
        if self._dispatched_rev == at:
            self._pace_stuck_at = at
        self.paced_writes += 1
        return time.perf_counter() - t0

    def watch_plane_reader(self) -> Callable[[], Dict[str, Any]]:
        """A reader of the watch plane's counters with a baseline of its
        own: each call gives `watch_evictions`, the streams cut off as deaf
        since its previous call, `pump_lag_max`, the largest pump lag
        since then (the lag right now included), and what the broadcast
        cost since then: `pump_busy_s`, the pump thread's own CPU seconds
        (on one interpreter they are seconds the writer did not have),
        `pump_turns`, its turns that broadcast, and `pump_events`, the
        events they carried. A scheduler calls it at each wave's end, so
        the wave's record says how far the broadcast fell behind the writes
        while it ran and what it took from them; two readers on one store
        (an active and a standby scheduler) do not take each other's
        maxima."""
        mark, seen = [0], [self.deaf_evictions]
        self._lag_marks += (mark,)
        pump = [self.pump_busy_s, self.pump_turns, self.pump_events]

        def read() -> Dict[str, Any]:
            now = max(self.kv.rev() - self._dispatched_rev, 0)
            top, mark[0] = max(mark[0], now), 0
            cut, seen[0] = self.deaf_evictions - seen[0], self.deaf_evictions
            was = tuple(pump)
            pump[:] = self.pump_busy_s, self.pump_turns, self.pump_events
            return {"watch_evictions": cut, "pump_lag_max": top,
                    "pump_busy_s": pump[0] - was[0],
                    "pump_turns": pump[1] - was[1],
                    "pump_events": pump[2] - was[2]}

        return read

    def live_watchers(self, prefix: str = "") -> int:
        """Registered, not-yet-stopped streams under prefix — the bench's
        `upstream_watches_per_resource` reads this (one mux stream per
        resource for a whole tenant fleet is the acceptance bar)."""
        with self._watch_mu:
            return sum(1 for wr in self._watchers
                       if not wr.watch.stopped
                       and wr.prefix.startswith(prefix))

    # ------------------------------------------------------------------ #
    # CRUD (etcd3 store.go Create:143 / Get:86 / Delete / GuaranteedUpdate:219)
    # ------------------------------------------------------------------ #

    def create(self, key: str, obj: Obj, resource: str = "object") -> Obj:
        """Store `obj` under a key that must not exist. The caller gives
        `obj` up: it is returned, with its new resourceVersion set, and is
        the caller's again. The store keeps the bytes, never the object."""
        t0 = time.perf_counter()
        kv_s = paced_s = 0.0
        try:
            data = _encode(obj)
            t1 = time.perf_counter()
            rev = self.kv.txn_put(key, 0, data)
            kv_s = time.perf_counter() - t1
            if rev < 0:
                raise errors.new_already_exists(resource, meta.name(obj))
            meta.set_resource_version(obj, str(rev))
            paced_s = self._pace(rev)
            return obj
        finally:
            _txn_done(_OP_CREATE, t0, kv_s, paced_s)

    def get(self, key: str, resource: str = "object", name: str = "") -> Obj:
        rec = self.kv.get(key)
        if rec is None:
            raise errors.new_not_found(resource, name or key)
        return _decode(rec.value, rec.mod_rev)

    def list(self, prefix: str, predicate: Predicate = None) -> Tuple[List[Obj], str]:
        t0 = time.perf_counter()
        kv_s = 0.0
        try:
            recs, at_rev = self.kv.range(prefix)
            kv_s = time.perf_counter() - t0
            items = []
            for rec in recs:
                obj = _decode(rec.value, rec.mod_rev)
                if predicate is None or predicate(obj):
                    items.append(obj)
            return items, str(at_rev)
        finally:
            _txn_done(_OP_LIST, t0, kv_s, span="store.list")

    def count(self, prefix: str) -> int:
        return self.kv.count(prefix)

    def delete(self, key: str, resource: str = "object", name: str = "",
               expected_rv: Optional[str] = None) -> Obj:
        t0 = time.perf_counter()
        paced_s = 0.0
        try:
            out, rev = self._delete(key, resource, name, expected_rv)
            paced_s = self._pace(rev)
            return out
        finally:
            _txn_done(_OP_DELETE, t0, paced_s=paced_s)

    def _delete(self, key: str, resource: str, name: str,
                expected_rv: Optional[str]) -> Tuple[Obj, int]:
        while True:
            rec = self.kv.get(key)
            if rec is None:
                raise errors.new_not_found(resource, name or key)
            if expected_rv is not None and str(rec.mod_rev) != expected_rv:
                raise errors.new_conflict(resource, name or key,
                                          "the object has been modified")
            rv = self.kv.txn_delete(key, rec.mod_rev)
            if rv > 0:
                return _decode(rec.value, rec.mod_rev), rv
            if rv == 0:
                raise errors.new_not_found(resource, name or key)
            # lost a race with a concurrent update; retry

    def guaranteed_update(self, key: str, update_fn: Callable[[Obj], Obj],
                          resource: str = "object", name: str = "",
                          ignore_not_found: bool = False,
                          expected_rv: Optional[str] = None) -> Obj:
        """Retry loop: read → user transform → CAS write (store.go:219-300).

        update_fn receives the current object (resourceVersion set; `{}`
        where `ignore_not_found` found none) and returns the new one, or
        raises to abort. What it is given is decoded for this attempt
        alone: it may change it, and return it. What it returns is the
        store's until the call ends: encoded, stamped with the new
        resourceVersion and returned, the caller's own from then on. An
        attempt that loses its CAS reads and decodes again, so a changed
        object is never handed out twice.
        """
        t0 = time.perf_counter()
        kv_s = [0.0]
        paced_s = 0.0
        try:
            out, rev = self._guaranteed_update(
                key, update_fn, resource, name, ignore_not_found,
                expected_rv, kv_s)
            paced_s = self._pace(rev)
            return out
        finally:
            _txn_done(_OP_UPDATE, t0, kv_s[0], paced_s)

    def _guaranteed_update(self, key: str, update_fn: Callable[[Obj], Obj],
                           resource: str, name: str, ignore_not_found: bool,
                           expected_rv: Optional[str],
                           kv_s: List[float]) -> Tuple[Obj, int]:
        chaos_cas = False  # at most one injected conflict per call: the
        # retry loop must converge even under FAULT_SPEC=store.cas_conflict@1.0
        while True:
            t1 = time.perf_counter()
            rec = self.kv.get(key)
            kv_s[0] += time.perf_counter() - t1
            if rec is None:
                if not ignore_not_found:
                    raise errors.new_not_found(resource, name or key)
                cur: Obj = {}
                cur_mod = 0
            else:
                cur = _decode(rec.value, rec.mod_rev)
                cur_mod = rec.mod_rev
            if (expected_rv is not None and rec is not None
                    and str(rec.mod_rev) != expected_rv):
                # the precondition holds on EVERY iteration, not just the
                # first: when our txn_put loses the CAS race to a
                # concurrent writer, the retry re-reads a revision past
                # the caller's precondition and MUST conflict — retrying
                # with the stale body would silently stomp the winner
                # (observed: a lease renew racing a usurper's claim
                # overwrote it and kept the incumbent leading — the exact
                # window lease fencing closes). etcd3 store.go preconditions
                # are checked per attempt for the same reason.
                raise errors.new_conflict(
                    resource, name or key,
                    "the object has been modified; please apply your changes "
                    "to the latest version and try again")
            if faultline.should("store.latency", "guaranteed_update"):
                # chaos: the storage backend (etcd) is slow — every hit
                # read-transform-write stalls KTPU_SLOW_S. The bind-intent
                # writes and Lease renews ride this path, so the overload
                # drills use it to slow the COMMIT side without touching
                # the watch/ingest side.
                import os as _os
                import time as _time

                _time.sleep(float(_os.environ.get("KTPU_SLOW_S", "0.2")))
            updated = update_fn(cur)
            if not chaos_cas and faultline.should("store.cas_conflict",
                                                  "guaranteed_update"):
                # chaos: behave exactly as if a concurrent writer won the
                # CAS race — skip the put and take the re-read/retry path
                chaos_cas = True
                continue
            data = _encode(updated)
            t1 = time.perf_counter()
            rev = self.kv.txn_put(key, cur_mod if cur_mod else 0, data)
            kv_s[0] += time.perf_counter() - t1
            if rev > 0:
                meta.set_resource_version(updated, str(rev))
                return updated, rev
            # CAS failure → re-read and retry

    # ------------------------------------------------------------------ #
    # Compaction
    # ------------------------------------------------------------------ #

    def compact_to(self, at_rev: int) -> None:
        """A REAL compaction at `at_rev`: the KV history and the cacher ring
        both drop everything at/below it, and every bookmark-opted LIVE
        watcher immediately receives a BOOKMARK carrying a revision ABOVE
        the new floor — the compaction-boundary crossing bookmark. That is
        what turns a later reconnect into a resume instead of a 410 relist:
        a quiet stream's resume token would otherwise sit below the floor
        exactly when the apiserver is busiest (the self-inflicted
        list-storm ISSUE 13 exists to kill)."""
        self.kv.compact(at_rev)
        self.watch_cache.compact(at_rev)
        self._send_bookmarks(trigger="compaction")

    # ------------------------------------------------------------------ #
    # Watch
    # ------------------------------------------------------------------ #

    def watch(self, prefix: str, since_rv: str = "",
              predicate: Predicate = None,
              bookmarks: bool = False,
              buffer: Optional[int] = None) -> mwatch.Watch:
        """Watch events under prefix with revision > since_rv.

        since_rv ""/"0" = from now. Raises Gone(410) if since_rv predates
        compaction — the caller must relist (reflector relist semantics).
        `buffer` bounds this watcher's delivery queue (default
        KTPU_WATCH_BUFFER); a consumer that leaves it full is delivered
        late, and cut off once it has for DEAF_AFTER_S; it never stalls
        the pump.
        """
        if faultline.should("store.compact", "watch"):
            # chaos: a REAL compaction at the current revision — stale
            # resumes below earn a genuine 410, and the dispatch pump's own
            # compaction handling runs against true state, not a mock. The
            # cacher ring compacts with it (a sustained storm churns old
            # revisions out of the window organically).
            self.compact_to(self.kv.rev())
        # per-call buffers go through the same clamp as the ctor/env path:
        # `buffer or ...` would send 0 to the default instead of the
        # documented clamp-to-1, and a negative value would make the queue
        # UNBOUNDED — un-evictable deaf consumers
        w = mwatch.Watch(capacity=_parse_watch_buffer(
            buffer, default=self._watch_buffer))
        with self._watch_mu:
            # "" / "0" = from NOW: the current store revision, regardless of
            # how far the dispatch pump has gotten
            since = int(since_rv) if since_rv not in ("", "0") else self.kv.rev()
            wr = _Watcher(prefix=prefix, watch=w, predicate=predicate,
                          since=since, bookmarks=bookmarks)
            # catch-up: replay history up to the pump's revision, as much as
            # the buffer holds, under the same lock the pump uses, so no
            # event is missed or duplicated; the pump brings the rest, then
            # everything newer. Served from the watch cache whenever `since`
            # is within its horizon — no storage read per watcher
            # (cacher.go:369-374)
            try:
                if since < self.watch_cache.horizon \
                        and since < self.kv.compacted_rev():
                    # neither the ring nor the log holds what follows
                    # `since`, wherever the pump is
                    raise native.CompactedError(since)
                self._top_up(wr)
            except native.CompactedError:
                raise errors.new_gone(
                    f"too old resource version: {since} "
                    f"(compacted at {self.kv.compacted_rev()})")
            self._lagging |= wr.since < self._dispatched_rev
            self._watchers.append(wr)
        return w

    @staticmethod
    def _to_cached(ev: native.KVEvent) -> CachedEvent:
        return CachedEvent(ev.rev, _EVENT_TYPES[ev.type], ev.key, ev.value)

    def _feed(self, wr: _Watcher, events, upto: int) -> None:
        """Hand `events` (revision order) to one watcher, in order, until
        its buffer is full; `since` moves with every event handed over or
        filtered out, and on to `upto` once none is left. If the buffer
        fills first the watcher lags from `since`. Never blocks: the event
        path for everyone else never stalls on one consumer (cacher.go
        dispatchEvent's non-blocking first pass)."""
        w = wr.watch
        free = w.capacity - w.depth()
        for ce in events:
            if ce.rev <= wr.since or not ce.key.startswith(wr.prefix):
                continue
            if wr.predicate is not None and not wr.predicate(ce.obj):
                wr.since = ce.rev
                continue
            # every buffer holds the one CachedEvent; the consumer's side
            # decodes an object of its own from it (cacher.CachedEvent.event)
            if free <= 0 or not w.offer(ce):
                return
            free -= 1
            wr.since = ce.rev
        wr.since = max(wr.since, upto)

    def _top_up(self, wr: _Watcher) -> None:
        """Bring a watcher that is behind the pump what its buffer has room
        for: from the cacher ring, beneath the ring's horizon from the KV
        history (CompactedError when compaction has taken what it needs).
        One that has not drained to half waits — each read of the ring or
        the log then fills at least half a buffer, and reads no more than
        the buffer has room for. Watch lock held."""
        w = wr.watch
        if wr.since >= self._dispatched_rev or w.stopped:
            wr.counted = False
            return
        free = w.capacity - w.depth()
        if free <= 0:
            now = time.monotonic()
            if not wr.full_since:
                wr.full_since = now
            elif now - wr.full_since >= self.deaf_after_s:
                self._evict(wr)
            return
        wr.full_since = 0.0  # it made room: slow, not deaf
        if 2 * free < w.capacity:
            return
        # one event more than there is room for says whether this refill
        # brings the watcher level
        events = self.watch_cache.events_since(
            wr.since, wr.prefix, limit=free + 1, count=not wr.counted)
        wr.counted = True
        if events is None:
            # the log may run ahead of the pump: those are the pump's to bring
            events = [self._to_cached(ev) for ev in self.kv.events_since(
                wr.since, wr.prefix, free + 1)
                if ev.rev <= self._dispatched_rev]
        whole = len(events) <= free
        self._feed(wr, events[:free], self._dispatched_rev if whole else 0)

    def _evict(self, wr: _Watcher) -> None:
        """Cut off a deaf consumer. The terminal Status survives the full
        buffer (machinery/watch.Watch.terminate), so a slow-but-alive
        consumer drains its backlog and THEN learns why and what to do:
        resume from its resourceVersion (504) while the events past
        `since` still exist, relist (410) once compaction has taken them."""
        w = wr.watch
        floor = self.kv.compacted_rev()
        if wr.since < floor:
            status = _too_old_status(
                f"{wr.since} (watcher evicted: delivery buffer of "
                f"{w.capacity} exhausted, compacted at {floor})")
        else:
            status = errors.new_timeout(
                f"watcher evicted: delivery buffer of {w.capacity} full for "
                f"{self.deaf_after_s:g}s; resume from resourceVersion "
                f"{wr.since}").status()
        w.terminate(mwatch.Event(mwatch.ERROR, status))
        self.deaf_evictions += 1
        WATCH_DEAF_EVICTIONS.inc(resource=wr.resource)

    def _send_bookmarks(self, trigger: str = "timer") -> None:
        with self._watch_mu:
            for wr in self._watchers:
                # a watcher that lags has events of its own still to come:
                # a bookmark at the pump's revision would let it resume
                # past them. It gets its bookmarks again once it is level.
                if wr.bookmarks and not wr.watch.stopped \
                        and wr.since >= self._dispatched_rev:
                    # `since` is at/above the pump's dispatched revision
                    # here and never ABOVE what was broadcast to this
                    # watcher: advertising the compaction floor itself
                    # when it outran the pump would hand out a resume
                    # token that silently skips events destroyed before
                    # they were ever broadcast. For a compaction at <=
                    # dispatched_rev (compact_to's contract for the seam
                    # and drills) this value already sits at/above the
                    # new floor, which is what makes the reconnect a
                    # resume; a floor beyond the pump leaves tokens below
                    # it, and the next resume earns its honest 410.
                    if wr.watch.offer(mwatch.Event(mwatch.BOOKMARK, {
                            "kind": "Bookmark", "apiVersion": "v1",
                            "metadata": {"resourceVersion": str(wr.since)}})):
                        self.bookmarks_sent += 1
                        if trigger == "compaction":
                            self.compaction_bookmarks += 1
                        WATCH_BOOKMARKS_SENT.inc(trigger=trigger)

    def _export_depths(self) -> None:
        """Deepest live delivery buffer per resource → watch_buffer_depth.
        Called from the pump with the watch lock held."""
        deepest: Dict[str, int] = {}
        for wr in self._watchers:
            if not wr.watch.stopped:
                d = wr.watch.depth()
                if d >= deepest.get(wr.resource, -1):
                    deepest[wr.resource] = d
        for res in self._depth_resources - set(deepest):
            WATCH_BUFFER_DEPTH.set(0, resource=res)
        self._depth_resources = set(deepest)
        for res, d in deepest.items():
            WATCH_BUFFER_DEPTH.set(d, resource=res)

    def _dispatch_loop(self) -> None:
        last_bm = time.monotonic()
        turn_end = export_at = 0.0  # last broadcast's end; next depth export
        while not self._stop.is_set():
            at = self._dispatched_rev
            # a watcher behind the pump is topped up as its consumer
            # makes room, writes or no writes: a short turn while one lags
            rev = self.kv.wait(at, timeout=0.02 if self._lagging else 0.25)
            now = time.monotonic()
            if 0 < rev - at < GATHER_EVENTS and now - turn_end < GATHER_S:
                # writes stream in (the previous turn has only just ended):
                # let them gather, asleep and off the writer's interpreter,
                # and pay this turn once for all. A plain sleep: waiting in
                # the KV for the count to fill wakes this thread at every
                # write, which costs the writer a quarter of a Binding
                self._stop.wait(GATHER_S)
                rev = self.kv.rev()
                now = time.monotonic()
            if faultline.should("watch.compact", "floor"):
                # chaos (ISSUE 13): a compaction storm hitting mid-stream —
                # a REAL compaction at the pump's own dispatched revision
                # (already-broadcast history only: compacting the kv head
                # would destroy events the pump hasn't read and force the
                # fell-behind 410 on everyone), with the boundary-crossing
                # bookmark broadcast that keeps LIVE opted-in streams
                # resumable. The drill asserts resumes, not relists,
                # survive this.
                self.compact_to(self._dispatched_rev)
            if now - last_bm >= self._bookmark_interval:
                last_bm = now
                self._send_bookmarks(trigger="timer")
            events = self._read_log(rev) if rev > at else ()
            with self._watch_mu:
                # what a turn owes every watcher it pays only when it has
                # to: one that lags or stopped (a broadcast says so), or on
                # a turn with nothing else to do
                tend = self._broadcast(events) if events else True
                if tend or self._lagging:
                    self._tend()
                if not events or now >= export_at:
                    export_at = now + EXPORT_EVERY_S
                    self._export_depths()
            if self._pace_waiting:
                with self._pace_cv:
                    self._pace_cv.notify_all()
            busy = time.thread_time()
            WATCH_PUMP_BUSY.inc(busy - self.pump_busy_s)
            self.pump_busy_s = busy
            if events:
                self.pump_turns += 1
                self.pump_events += len(events)
                WATCH_PUMP_TURNS.inc()
                WATCH_PUMP_EVENTS.inc(len(events))
                turn_end = time.monotonic()

    def _tend(self) -> None:
        """Bring each watcher that is behind the pump what its buffer has
        room for, drop the streams that stopped, and say whether any still
        lags. Watch lock held."""
        at = self._dispatched_rev
        live, lagging = [], False
        for wr in self._watchers:
            if wr.since < at or wr.watch.stopped:
                try:
                    self._top_up(wr)
                except native.CompactedError:
                    # compaction took events this watcher was still
                    # owed: the one real gap, and only its own
                    wr.watch.terminate(mwatch.Event(
                        mwatch.ERROR, _too_old_status(
                            f"{wr.since} (compacted at "
                            f"{self.kv.compacted_rev()})")))
                if wr.watch.stopped:
                    continue
                if wr.since < at:
                    lagging = True
                else:
                    wr.counted = False  # level: its next catch-up is new
            live.append(wr)
        self._watchers = live
        self._lagging = lagging

    def _read_log(self, head: int):
        """Every event past the dispatched revision, and the pump's lag as
        this turn found it. Empty when compaction outran the pump: every
        watcher has then been sent to relist."""
        lag = head - self._dispatched_rev
        for mark in self._lag_marks:
            mark[0] = max(mark[0], lag)
        WATCH_PUMP_LAG.set(lag)
        try:
            events = self.kv.events_since(self._dispatched_rev, "")
        except native.CompactedError:
            # the pump fell behind compaction: watchers have an
            # unrecoverable gap — error them all out so clients relist
            # (the reference terminates such watchers, cacher.go)
            with self._watch_mu:
                gone = errors.new_gone(
                    "watch events compacted away; relist required")
                for wr in self._watchers:
                    wr.watch.terminate(
                        mwatch.Event(mwatch.ERROR, gone.status()))
                self._watchers.clear()
                self._dispatched_rev = self.kv.rev()
                # the compacted-away events never reached the ring: the
                # cache has a GAP, so its window must restart at now —
                # otherwise a later resume would be served an incomplete
                # history instead of falling through to a 410
                self.watch_cache = WatchCache(
                    horizon=self._dispatched_rev)
            return ()
        if len(events) > self.pump_batch_max:
            self.pump_batch_max = len(events)
            WATCH_PUMP_BATCH_MAX.set(len(events))
        return events

    def _broadcast(self, events) -> bool:
        """One turn's events into the cacher ring and to each watcher that
        is level with the pump, as far as its buffer has room (one that
        fills lags from there and is topped up in turns of its own). True
        when the turn has a watcher to tend: one whose buffer filled, or
        whose stream stopped. Watch lock held."""
        cached = [self._to_cached(ev) for ev in events]
        self.watch_cache.extend(cached)
        head = cached[-1].rev
        tend = False
        for wr in self._watchers:
            # level with the pump (or registered "from now", ahead of it):
            # this batch is its next; one that lags is owed older events
            # first
            if wr.watch.stopped:
                tend = True
            elif wr.since >= self._dispatched_rev:
                self._feed(wr, cached, head)
                tend |= wr.since < head
        self._dispatched_rev = head
        return tend
