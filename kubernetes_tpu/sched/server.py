"""kube-scheduler, the process: informer wiring + scheduling loop + binder.

Analog of `cmd/kube-scheduler/app/server.go` (Run :167) +
`pkg/scheduler/eventhandlers.go` (AddAllEventHandlers :335): watches pods
and nodes, feeds the batched TPU scheduling core
(kubernetes_tpu.sched.scheduler.Scheduler), binds via the pods/binding
subresource, records FailedScheduling events, and optionally runs behind
leader election like the reference binary (:254-260).
"""

from __future__ import annotations

import contextlib
import logging
import queue
import threading
import time
from typing import Any, Dict, Optional

from kubernetes_tpu.api.types import (
    Affinity,
    Node,
    Pod,
)
from kubernetes_tpu.api.v1 import (csinode_volume_limits, node_from_v1,
                                   pod_from_v1)
from kubernetes_tpu.client.events import EventBroadcaster
from kubernetes_tpu.client.informers import SharedInformer
from kubernetes_tpu.client.leaderelection import (
    LeaderElectionConfig,
    LeaderElector,
)
from kubernetes_tpu.component import trace
from kubernetes_tpu.machinery import errors, meta
from kubernetes_tpu.sched.metrics import (
    FAILED_EVENTS,
    START_FROZEN,
    START_UNSYNCED,
)
from kubernetes_tpu.sched.scheduler import Scheduler

logger = logging.getLogger("kubernetes_tpu.sched.server")

Obj = Dict[str, Any]


def start_informer(informer: SharedInformer, telemetry, stage: str,
                   component: str) -> bool:
    """Start `informer` and wait for its initial list, as one stage of a
    server's start: the wait is filed under `stage` of the telemetry's loop
    account (`start/pods-sync`), the round's own stages (`last_sync`:
    `list`, `index`, `handlers` and what filed itself below them) under
    it. A start goes on over an informer that has not synced in time, as
    it always has; that is logged, counted and kept on the account."""
    informer.trace_below = telemetry.enabled  # off: nothing below a stage
    synced = informer.start().wait_for_sync()
    sync = informer.last_sync if synced else None
    telemetry.loop_stage(stage, synced=synced,
                         below=sync["children"] if sync else None)
    if not synced:
        resource = informer.rc.resource
        logger.warning("%s: the %s informer had not synced when its wait "
                       "ended: the start goes on over a partial view",
                       component, resource)
        START_UNSYNCED.inc(component=component, resource=resource)
    return synced


@contextlib.contextmanager
def initial_lists(telemetry, component: str):
    """The stretch of a server's start that lists the cluster, every
    `start_informer` of it and what is built from their lists before the
    first request: the cyclic collector stands aside for its length and
    what it made leaves the collector's walk (utils/platform.py
    `listing_heap`). What that took is kept on the start's account, as
    `loop.start_frozen_objects` and `loop.start_collector_off_s` of the
    first record, and on `scheduler_start_frozen_objects`."""
    from kubernetes_tpu.utils.platform import listing_heap

    with listing_heap() as took:
        yield
    START_FROZEN.set(took["frozen_objects"], component=component)
    telemetry.loop_note(
        start_frozen_objects=took["frozen_objects"],
        start_collector_off_s=round(took["collector_off_s"], 6))


def decoded(convert, obj: Obj):
    """`convert(obj)`, a v1 dict to the scheduler's type. Inside an
    informer's list+replace round (its Trace is `trace.current()` on the
    informer's thread) the conversion is the round's `handlers/decode`;
    a later watch event finds None and pays that one check."""
    tr = trace.current()
    if tr is None:
        return convert(obj)
    t0 = tr.clock()
    out = convert(obj)
    tr.child("decode", tr.clock() - t0)
    return out


class _HandlerLock:
    """One informer handler's turn on `SchedulerServer._mu`. While it waits
    for the lock (a wave holds it from pop to requeue) its thread stands in
    `waiting`, where the loop's peek sees it; as the last one through
    leaves it sets `through`, for which the loop then waits. With a
    telemetry (`tel`; None when it is off) the seconds it waited and the
    seconds it held the lock are counted, on the telemetry's clock."""

    __slots__ = ("mu", "waiting", "through", "tel", "t0", "t1")

    def __init__(self, mu, waiting: set, through, tel) -> None:
        self.mu = mu
        self.waiting = waiting
        self.through = through
        self.tel = tel

    def __enter__(self) -> None:
        me = threading.get_ident()
        tel = self.tel
        self.waiting.add(me)
        if tel is not None:
            self.t0 = tel.clock()
        self.mu.acquire()
        self.waiting.discard(me)
        if tel is not None:
            self.t1 = tel.clock()

    def __exit__(self, *exc) -> None:
        tel = self.tel
        if tel is not None:
            held = tel.clock() - self.t1
        self.mu.release()
        if not self.waiting and not self.through.is_set():
            self.through.set()
        if tel is not None:
            tel.note_handler(self.t1 - self.t0, held)


class BindWindow:
    """A binder's writes on threads of their own, `width` of them: a wave
    hands each Binding to one (`submit`), keeps at most `width` outstanding
    and takes the answers as they come (`gather`); `Scheduler.commit_wave`
    is the one caller, from the one thread that commits. A write is the
    binder's own `bind`, whole: its fence stamp, its retry budget, its
    one request through the client's transport, on the connection that
    transport keeps for the calling thread, so a thread here is a
    kept-alive connection. The threads start at the first `submit`, each
    takes room in its own arena (utils/platform.py `steady_heap`: it
    allocates through every wave from then on), and they live until
    `close`.

    A thread of this window has no `trace.current()` of the wave's, so a
    write of a traced wave files what lies below it (`http.request`) on a
    Trace of its thread's, and the wave takes them all (`spans`) once its
    last answer is in, to graft below its own `bind-call`."""

    def __init__(self, bind, width: int):
        self.width = width
        self._bind = bind
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        self._answers: queue.SimpleQueue = queue.SimpleQueue()
        self._traces: list = [None] * width
        self._threads: list = []

    def submit(self, tag, pod: Pod, node_name: str, traced: bool) -> None:
        """Hand one write out; its answer comes back under `tag`."""
        if not self._threads:
            self._threads = [threading.Thread(
                target=self._work, args=(i,), name=f"bind-window-{i}",
                daemon=True) for i in range(self.width)]
            for t in self._threads:
                t.start()
        self._jobs.put((tag, pod, node_name, traced))

    def gather(self):
        """Wait for the next answer: `(tag, ok, seconds)`, the seconds the
        write's own, from its thread taking it to `bind` returning."""
        tag, ok, seconds = self._answers.get()
        if isinstance(ok, BaseException):
            raise ok   # nothing an `except Exception` takes: the wave's
        return tag, ok, seconds

    def spans(self) -> list:
        """The `children()` of each thread's Trace since the last call, for
        the caller to graft; only with nothing outstanding."""
        out = [tr.children() for tr in self._traces if tr is not None]
        self._traces = [None] * self.width
        return out

    def close(self) -> None:
        threads, self._threads = self._threads, []
        for _ in threads:
            self._jobs.put(None)
        for t in threads:
            t.join(timeout=2)

    def _work(self, i: int) -> None:
        from kubernetes_tpu.utils.platform import steady_heap

        steady_heap()
        pc = time.perf_counter
        while True:
            job = self._jobs.get()
            if job is None:
                return
            tag, pod, node_name, traced = job
            token = None
            if traced:
                if self._traces[i] is None:
                    self._traces[i] = trace.Trace("bind-window", clock=pc)
                token = trace.activate(self._traces[i])
            t0 = pc()
            try:
                ok = bool(self._bind(pod, node_name))
            except Exception:  # noqa: BLE001 - a raising binder is a refusal
                ok = False
            except BaseException as e:  # noqa: BLE001 - raised at the gather
                ok = e
            seconds = pc() - t0
            if token is not None:
                trace.deactivate(token)
            self._answers.put((tag, ok, seconds))


class APIBinder:
    """Binder over POST pods/{name}/binding (scheduler.go:565). When volume
    binding is wired (`SchedulerServer.start()` hands it the server's
    `volume_binder`), BindPodVolumes runs first for a pod that names claims
    (scheduler.go:660,517; the span `bind-call/volume-bind`) and a volume
    failure aborts the pod bind → assume rollback.

    Fenced: with a `fence_source` attached (leader election), every Binding
    is stamped with the current lease generation so the apiserver can
    reject a deposed leader's write (api.types.FENCING_TOKEN_ANNOTATION;
    apiserver/server.py `bind_pod`).

    Retry budget (ISSUE 9): server PUSHBACK — 429 TooManyRequests from the
    max-inflight filter, 503 from a restarting apiserver — is retried
    through ONE shared implementation of the backoff semantics
    (client/rest.py RetryPolicy: capped exponential + jitter, the Status'
    `retryAfterSeconds` honored as a floor, per-bind deadline). Both 429
    and 503 are rejected BEFORE the Binding mutates anything, so the
    retry can never double-apply. Everything else (fenced 409,
    already-assigned, NotFound) still fails fast — persistent pushback
    past the budget is the commit breaker's job (sched/overload.py),
    not the binder's.

    How many of a wave's Bindings may be in flight at once is the
    transport's to say (client/rest.py `writes_in_flight`): `window()` is
    None where it says one (`LocalTransport`: the wave calls `bind` in its
    turn), else a `BindWindow` of that width over `bind`, which any number
    of threads may call at once."""

    def __init__(self, client, volume_binder=None,
                 fence_source=None,
                 fence_lease: str = "",
                 retry_budget: int = 3,
                 retry_base_s: float = 0.05,
                 retry_cap_s: float = 1.0,
                 bind_deadline_s: float = 3.0):
        from kubernetes_tpu.api.types import DEFAULT_FENCING_LEASE
        from kubernetes_tpu.client.rest import RetryPolicy

        self.client = client
        self.volume_binder = volume_binder
        self.fence_source = fence_source  # () -> int lease generation
        self.fence_lease = fence_lease or DEFAULT_FENCING_LEASE
        self.stale_rejects = 0  # fenced-off binds (the mechanism working)
        self.pushback_retries = 0  # 429/503 absorbed by the budget
        self.pushback_failures = 0  # budget/deadline exhausted
        self._counts_mu = threading.Lock()   # the three counts above
        self._window: Optional[BindWindow] = None
        self.retry = RetryPolicy(attempts=retry_budget, base_s=retry_base_s,
                                 cap_s=retry_cap_s,
                                 deadline_s=bind_deadline_s,
                                 on_retry=self._note_pushback_retry)

    def _note_pushback_retry(self) -> None:
        with self._counts_mu:
            self.pushback_retries += 1

    def window(self) -> Optional[BindWindow]:
        """The window a wave's Bindings go out through, or None where the
        transport takes one write at a time."""
        width = getattr(getattr(self.client, "transport", None),
                        "writes_in_flight", 1)
        if width <= 1:
            return None
        if self._window is None:
            self._window = BindWindow(self.bind, width)
        return self._window

    def close(self) -> None:
        """End the window's threads (the scheduler's stop)."""
        if self._window is not None:
            self._window.close()

    def bind(self, pod: Pod, node_name: str) -> bool:
        from kubernetes_tpu.api.types import (FENCED_BIND_MARKER,
                                              FENCING_LEASE_ANNOTATION,
                                              FENCING_TOKEN_ANNOTATION)

        if self.volume_binder is not None and pod.claims:
            tr = trace.current()
            t0 = time.perf_counter()
            ok = self.volume_binder.bind(pod, node_name)
            if tr is not None:
                tr.child("volume-bind", time.perf_counter() - t0)
            if not ok:
                return False
        annotations = None
        if self.fence_source is not None:
            annotations = {
                FENCING_TOKEN_ANNOTATION: str(int(self.fence_source())),
                FENCING_LEASE_ANNOTATION: self.fence_lease,
            }
        try:
            self.retry.run(lambda: self.client.pods.bind(
                pod.name, node_name, pod.namespace,
                uid=pod.uid, annotations=annotations))
            return True
        except errors.StatusError as e:
            with self._counts_mu:
                if annotations is not None and errors.is_conflict(e) \
                        and FENCED_BIND_MARKER in str(e):
                    self.stale_rejects += 1
                elif e.code in (429, 503):
                    self.pushback_failures += 1
            return False


class TelemetryGateway:
    """Scheduler-side scrape point (ISSUE 7): the apiserver already serves
    the shared registry at its /metrics, but the scheduler is its own
    process in production — it needs its own exposition. Serves

      /metrics               component/metrics.py text format (the shared
                             DEFAULT_REGISTRY: scheduler_* series included)
      /debug/flightrecorder  the flight-recorder ring as structured JSON
                             (read-only: the same document shape an
                             auto-dump writes, with none of the dump
                             side effects)
      /debug/compiles        what XLA cost this process: the account's
                             four totals and its kept entries, oldest
                             first (sched/telemetry.py XlaAccount; ISSUE
                             51): which program, for whom, and why
      /debug/why/<ns>/<pod>  the pod's latest decision attribution
                             (ISSUE 10: reason counts, top-k candidates
                             with score decomposition, queue lane +
                             attempts + first-seen age) — requires a
                             `scheduler` and its KTPU_EXPLAIN explainer
      /healthz               "ok"

    on a daemonized stdlib HTTP server; port 0 binds an ephemeral port."""

    def __init__(self, telemetry, host: str = "127.0.0.1", port: int = 0,
                 scheduler=None):
        import http.server
        import json as _json
        import socketserver

        from kubernetes_tpu.component.metrics import DEFAULT_REGISTRY

        tel = telemetry
        sched = scheduler

        def _why_doc(ns: str, name: str):
            """The why-pending document, assembled read-only from the
            explainer's latest attribution, the queue lane and the e2e
            tracker's first-seen stamp. None when the pod is entirely
            unknown (404)."""
            key = f"{ns}/{name}"
            doc: Dict[str, Any] = {"pod": key}
            attribution = None
            if getattr(sched, "explainer", None) is not None:
                attribution = sched.explainer.why(key)
                doc["explain_enabled"] = True
            else:
                doc["explain_enabled"] = False
            lane, attempts = sched.queue.describe(key)
            doc["queue_lane"] = lane
            doc["attempts"] = attempts
            first = tel.tracker.first_seen(key)
            doc["first_seen_age_s"] = (
                round(sched.clock() - first, 6) if first is not None
                else None)
            if attribution is not None:
                doc["attribution"] = attribution
            if attribution is None and lane is None and first is None:
                return None
            return doc

        class _Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):  # noqa: ARG002 - silence stdlib
                pass

            def do_GET(self):  # noqa: N802 - stdlib handler name
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    body = DEFAULT_REGISTRY.expose_text().encode()
                    ctype = "text/plain; version=0.0.4"
                elif path == "/debug/flightrecorder":
                    # read-only: a scrape loop must not clobber last_dump,
                    # count as a dump, or write KTPU_FLIGHT_DIR files
                    body = _json.dumps(
                        tel.snapshot_doc("debug-endpoint"), indent=1).encode()
                    ctype = "application/json"
                elif path == "/debug/compiles":
                    from kubernetes_tpu.sched.telemetry import xla_account

                    acct = xla_account()
                    body = _json.dumps(
                        {"totals": acct.totals(),
                         "entries": acct.entries()}, indent=1).encode()
                    ctype = "application/json"
                elif path.startswith("/debug/why/") and sched is not None:
                    parts = [p for p in path.split("/") if p][2:]
                    if len(parts) != 2:
                        self.send_error(404)
                        return
                    doc = _why_doc(parts[0], parts[1])
                    if doc is None:
                        self.send_error(404)
                        return
                    body = _json.dumps(doc, indent=1).encode()
                    ctype = "application/json"
                elif path == "/healthz":
                    body, ctype = b"ok", "text/plain"
                else:
                    self.send_error(404)
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        class _Server(socketserver.ThreadingMixIn, http.server.HTTPServer):
            daemon_threads = True

        self._httpd = _Server((host, port), _Handler)
        self.host, self.port = self._httpd.server_address
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="scheduler-telemetry-http",
                                        daemon=True)

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> "TelemetryGateway":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


def pod_schedulable_v1(obj: Obj) -> bool:
    """Is this v1 pod dict something a scheduler should (still) act on?
    Shared by SchedulerServer's informer handlers and the fleet watch
    plane's per-tenant ingest (fleet/server._TenantIngest) — ONE
    definition, so the two paths cannot drift."""
    phase = obj.get("status", {}).get("phase", "")
    return phase not in ("Succeeded", "Failed") and \
        not meta.is_being_deleted(obj)


def apply_pod_update_v1(scheduler: Scheduler, old: Obj, new: Obj,
                        to_pod, arrived: Optional[float] = None) -> None:
    """The informer pod-UPDATE transition (eventhandlers.go:335-441),
    against one Scheduler: a no-longer-schedulable pod either frees its
    node's resources (terminated on a node) or leaves the queue; a live
    one flows through on_pod_update. `to_pod` is the caller's v1→Pod
    conversion (it owns creation_index stamping). `arrived` is when the
    event reached the caller, on the scheduler's clock, if it read that
    before taking its lock. Callers provide their own locking. Shared by
    SchedulerServer and _TenantIngest."""
    if not pod_schedulable_v1(new):
        p = pod_from_v1(new)
        if p.node_name:
            # terminated on its node: free the resources
            if scheduler.cache.get_pod(p.key) is not None:
                scheduler.cache.remove_pod(p.key)
                scheduler.queue.move_all_to_active(scheduler.clock())
        else:
            scheduler.queue.delete(p.key)
        return
    scheduler.on_pod_update(pod_from_v1(old), to_pod(new), arrived)


class SchedulerServer:
    """The scheduler process: New + Run (scheduler.go:255,425-431)."""

    def __init__(self, client, scheduler: Optional[Scheduler] = None,
                 scheduler_name: str = "default-scheduler",
                 cycle_interval: float = 0.05,
                 batch_window: float = 0.02,
                 leader_elect: bool = False,
                 volume_binding: bool = True,
                 config=None,
                 base_dims=None,
                 batch_size: Optional[int] = None,
                 ledger=None,
                 lease_config: Optional[Dict[str, Any]] = None,
                 standby_warm_interval: float = 2.0,
                 telemetry_port: Optional[int] = None):
        from kubernetes_tpu.state.dims import Dims

        # ComponentConfig / Policy surface (apis/config/types.go:45-112 →
        # sched/config.py): a config file/dict drives scheduler name, plugin
        # composition + weights, extenders, backoff bounds, feature gates,
        # preemption, and leader election.
        self.config = None
        framework = None
        extenders = ()
        queue = None
        if config is not None and scheduler is not None:
            # a pre-built Scheduler already fixed its queue/framework/
            # extenders — applying only the remainder of the config would be
            # a silently half-applied configuration
            raise ValueError(
                "pass either a pre-built scheduler OR a config; a config's "
                "queue/framework/extender wiring cannot be grafted onto an "
                "existing Scheduler")
        if config is not None:
            from kubernetes_tpu.extender.client import HTTPExtender
            from kubernetes_tpu.sched.config import (
                KubeSchedulerConfiguration, load_config)
            from kubernetes_tpu.sched.queue import PriorityQueue

            self.config = (config if isinstance(config, KubeSchedulerConfiguration)
                           else load_config(config))
            self.config.apply_feature_gates()
            scheduler_name = self.config.scheduler_name
            framework = self.config.build_framework()
            extenders = tuple(HTTPExtender(e) for e in self.config.extenders)
            queue = PriorityQueue(
                initial_backoff=self.config.pod_initial_backoff_seconds,
                max_backoff=self.config.pod_max_backoff_seconds)
            leader_elect = leader_elect or self.config.leader_election.leader_elect

        self.client = client
        # FailedScheduling Events leave the loop through a queue: one sink
        # thread writes them, and it never takes `_mu`
        self.recorder = EventBroadcaster(
            client, component=scheduler_name,
            observe=lambda outcome, n: FAILED_EVENTS.inc(n, outcome=outcome))
        self.scheduler = scheduler or Scheduler(
            binder=APIBinder(client), scheduler_name=scheduler_name,
            queue=queue,
            framework=framework,
            extenders=extenders,
            # shape floor: tiny waves share one compiled (P,N,E) signature
            # instead of recompiling at every power-of-two batch size; a
            # caller expecting a large cluster pre-sizes (capacity
            # provisioning — avoids growth-bucket recompiles mid-flight),
            # and says how many pods one wave may pop
            base_dims=base_dims or Dims(N=64, P=128, E=512),
            **({} if batch_size is None else {"batch_size": batch_size}))
        if self.scheduler.binder is None:
            self.scheduler.binder = APIBinder(client)
        self.scheduler.events_pending = self.recorder.pending
        self.scheduler.watch_plane = self._watch_plane
        self._relists_seen = 0
        # the store's side of it, where the client can read the store's
        # counters (`Client.local`); None over HTTP
        counters = getattr(client, "store_counters", None)
        self._store_counters = counters() if counters is not None else None
        if self.config is not None:
            if self.config.decision_provenance:
                # config-file switch for the provenance pipeline (the env
                # alternative is KTPU_EXPLAIN); the event sink attaches in
                # start() with the informer lister
                self.scheduler.enable_explain()
            self.scheduler.hard_pod_affinity_weight = float(
                self.config.hard_pod_affinity_symmetric_weight)
            # the fused engines honor the plugin composition through traced
            # per-component weights/flags (ops/lattice.py EngineConfig)
            # (RequestedToCapacityRatio's weight map names resources: their
            # slots of the R axis are interned now, as NodeLabel's keys are)
            from kubernetes_tpu.api.types import NUM_FIXED_RES

            resources = self.scheduler.encoder.vocabs.resources
            slot = lambda name: NUM_FIXED_RES + resources.intern(name)
            self.scheduler.engine_config = self.config.engine_config(slot)
            # NodeLabel needs vocab ids for its configured keys; intern them
            # now so the ids are stable before any node arrives. A caller-
            # supplied Scheduler keeps its own framework (possibly None).
            fw = self.scheduler.framework
            for pl in (fw.score_plugins if fw is not None else ()):
                if type(pl).__name__ == "NodeLabel":
                    keys = self.scheduler.encoder.vocabs.label_keys
                    pl._present_ids = tuple(keys.intern(k) for k in pl.present)
                    pl._absent_ids = tuple(keys.intern(k) for k in pl.absent)
                if type(pl).__name__ == "RequestedToCapacityRatio":
                    pl.resource_slot = slot
        if scheduler is None and (self.config is None or
                                  not self.config.disable_preemption):
            from kubernetes_tpu.sched.preemption import APIEvictor, Preemptor

            # preemption is ON by default — DisablePreemption defaults
            # false (apis/config/types.go:76); only an explicit
            # disablePreemption: true (or a caller-built Scheduler) turns
            # it off. Victims are evicted THROUGH THE API (APIEvictor) —
            # the cache-only default evictor would free resources the
            # scheduler sees while the victim pod lives on in the
            # apiserver, double-booking its node. PDB lister for the
            # preemption what-if (filterPodsWithPDBViolation inputs) —
            # served from the PDB informer cache wired in start(), like
            # the reference's policy lister, never a synchronous LIST on
            # the preemption hot path
            self.scheduler.preemptor = Preemptor(
                evictor=APIEvictor(client),
                pdb_source=lambda: list(self._pdb_cache.values()))
        self.cycle_interval = cycle_interval
        # debounce: when pods flood in, wait this long so one batched device
        # wave absorbs them instead of many tiny waves (adds at most this
        # much latency to an isolated pod)
        self.batch_window = batch_window
        # volume binding (CheckVolumeBinding/NoVolumeZoneConflict +
        # WaitForFirstConsumer coordination); informers wired in start()
        self.volume_binding = volume_binding
        self.volume_binder = None
        self.pvc_informer = self.pv_informer = self.sc_informer = None
        self.csinode_informer = None
        # claim key -> keys of the BOUND pods that name it: a claim that
        # binds or changes after its pod was decoded is followed again
        self._bound_by_claim: Dict[str, set] = {}
        self.pdb_informer = None
        self._pdb_cache: Dict[str, tuple] = {}  # key → (ns, selector, allowed)
        self._creation_seq = 0
        self._stop = threading.Event()
        self._threads = []
        self._mu = threading.Lock()  # serializes event handlers vs waves
        # the handler threads that wait for `_mu` right now, and the signal
        # that the last of them is through (_HandlerLock): `_mu` is not
        # fair, so the loop lets them go first itself (`_gather`)
        self._handlers_waiting: set = set()
        self._handlers_through = threading.Event()
        self.pod_informer: Optional[SharedInformer] = None
        self.node_informer: Optional[SharedInformer] = None
        self.elector: Optional[LeaderElector] = None
        self._active = threading.Event()
        if leader_elect:
            self.elector = LeaderElector(client, LeaderElectionConfig(
                lock_name="kube-scheduler",
                on_started_leading=self._active.set,
                on_stopped_leading=self._on_stopped_leading,
                **(lease_config or {})))
            # fencing: the scheduler stamps the elector's lease generation
            # into intents; the API binder stamps it into Binding writes
            self.scheduler.fence_source = \
                lambda: self.elector.fencing_token
            if isinstance(self.scheduler.binder, APIBinder):
                self.scheduler.binder.fence_source = \
                    lambda: self.elector.fencing_token
        else:
            self._active.set()
        # exactly-once restart/HA (sched/ledger.py): with a ledger attached,
        # every (re)acquisition of leadership — including plain process
        # start — reconciles unretired bind intents BEFORE the first wave
        self.scheduler.ledger = ledger if ledger is not None \
            else self.scheduler.ledger
        self.standby_warm_interval = standby_warm_interval
        self._standby_last = 0.0
        self._needs_recover = self.scheduler.ledger is not None
        self.last_recovery = None      # RecoveryReport of the latest pass
        self.last_recovery_error = None
        self.takeovers = 0             # leadership activations that ran one
        self._crashed = False
        self.total_scheduled = 0
        self.total_unschedulable_events = 0
        # waves that raised (the loop survives them; a caller that must not
        # miss one reads these)
        self.wave_errors = 0
        self.last_wave_error: Optional[BaseException] = None
        # scheduler-side /metrics + /debug/flightrecorder exposition
        # (TelemetryGateway): None = off, 0 = ephemeral port, N = fixed
        self.telemetry_port = telemetry_port
        self.telemetry_gateway: Optional[TelemetryGateway] = None

    # -- conversion --------------------------------------------------------- #

    def _to_pod(self, obj: Obj) -> Pod:
        pod = pod_from_v1(obj)
        # stable FIFO-within-priority ordering (creationTimestamp analog)
        self._creation_seq += 1
        pod.creation_index = self._creation_seq
        if pod.claims and pod.node_name and self.volume_binder is not None:
            # a bound pod's claims count on its node as they stand (and
            # again when one of them changes: `_on_claim`); a pending
            # pod's are resolved by the wave that pops it
            for ref in pod.claims:
                self._bound_by_claim.setdefault(
                    f"{pod.namespace}/{ref.name}", set()).add(pod.key)
            pod.volumes += self.volume_binder.volumes_of(pod)
        return pod

    def _to_node(self, obj: Obj) -> Node:
        node = node_from_v1(obj)
        if self.csinode_informer is not None:
            # getMaxVolumeFunc: a driver's CSINode count, else allocatable
            csinode = self.csinode_informer.lister.get("", node.name)
            if csinode is not None:
                node.volume_limits.update(csinode_volume_limits(csinode))
        return node

    @property
    def _waiting_on_volumes(self) -> set:
        """Keys of the pods parked on their claims (the scheduler's
        `volume_waiting`)."""
        return self.scheduler.volume_waiting

    @staticmethod
    def _schedulable(obj: Obj) -> bool:
        return pod_schedulable_v1(obj)

    # -- event handlers (eventhandlers.go:335-441) --------------------------- #

    def _handling(self):
        """`with` target of an informer handler: its turn on `_mu`, seen
        by the loop while it waits for it (`_gather`); with telemetry on
        the wait and the hold are counted onto the next wave's record
        (`loop.handlers`). The PDB handlers take no lock and are not
        counted."""
        tel = self.scheduler.telemetry
        return _HandlerLock(self._mu, self._handlers_waiting,
                            self._handlers_through,
                            tel if tel.enabled else None)

    def _arrived(self) -> float:
        """When the pod event now being handled reached the scheduler, on
        the scheduler's clock: the handler's entry, read BEFORE the wait
        for `_mu`, less what the event waited in the informer's buffer
        (its one thread delivers in turn: while a handler waits out a
        wave, the events behind it have arrived too). What the queue
        stamps a new pod with, so its wait and the loop's gathering window
        count from here and not from the end of the wave it waited out."""
        now = self.scheduler.clock()
        inf = self.pod_informer
        return now - inf.delivery_lag() if inf is not None else now

    def _on_pod_add(self, obj: Obj) -> None:
        if not self._schedulable(obj):
            return
        arrived = self._arrived()
        with self._handling():
            self.scheduler.on_pod_add(decoded(self._to_pod, obj), arrived)

    def _on_pod_update(self, old: Obj, new: Obj) -> None:
        arrived = self._arrived()
        with self._handling():
            apply_pod_update_v1(self.scheduler, old, new, self._to_pod,
                                arrived)

    def _on_pod_delete(self, obj: Obj) -> None:
        with self._handling():
            pod = pod_from_v1(obj)
            for ref in pod.claims:
                claim = f"{pod.namespace}/{ref.name}"
                keys = self._bound_by_claim.get(claim)
                if keys is not None:
                    keys.discard(pod.key)
                    if not keys:
                        del self._bound_by_claim[claim]
            self.scheduler.on_pod_delete(pod)

    def _on_claim(self, obj: Obj) -> None:
        """A claim came or changed: the bound pods that name it are decoded
        again, so that their node counts the volume the claim stands for
        NOW; then what every volume event does. All of it under `_mu`: a
        pod handler decodes under it too, so either it read the claim
        lister after this event reached it, or it has registered its pod
        here by the time this looks."""
        key = f"{meta.namespace(obj) or 'default'}/{meta.name(obj)}"
        with self._handling():
            sched = self.scheduler
            for pod_key in tuple(self._bound_by_claim.get(key, ())):
                ns, name = meta.split_key(pod_key)
                cur = self.pod_informer.lister.get(ns, name)
                if cur is not None and pod_schedulable_v1(cur) \
                        and sched.cache.get_pod(pod_key) is not None \
                        and not sched.cache.is_assumed(pod_key):
                    sched.cache.update_pod(self._to_pod(cur))
            sched.queue.move_all_to_active(sched.clock())

    def _nodes_changed(self) -> None:
        if self.volume_binder is not None:
            self.volume_binder.nodes_changed()

    def _on_node_add(self, obj: Obj) -> None:
        with self._handling():
            self._nodes_changed()
            self.scheduler.on_node_add(decoded(self._to_node, obj))

    def _on_node_update(self, old: Obj, new: Obj) -> None:
        with self._handling():
            self._nodes_changed()
            self.scheduler.on_node_update(self._to_node(new))

    def _on_node_delete(self, obj: Obj) -> None:
        with self._handling():
            self._nodes_changed()
            self.scheduler.on_node_delete(meta.name(obj))

    def _on_csinode(self, obj: Obj) -> None:
        """A CSINode came, changed or went: its node's limits follow (the
        node handler reads the CSINode lister, which has the event)."""
        node = self.node_informer.lister.get("", meta.name(obj))
        if node is not None:   # None at the initial list: no node yet
            self._on_node_update(node, node)

    def _on_volume_event(self, obj: Obj) -> None:
        """A PersistentVolume(Claim) or StorageClass came or changed: pods
        that wait on a claim, or found no node their volumes reach, may go
        now (eventhandlers.go onPvAdd / onPvcAdd: MoveAllToActiveQueue)."""
        with self._handling():
            self.scheduler.queue.move_all_to_active(self.scheduler.clock())

    def _watch_plane(self) -> Dict[str, Any]:
        """What the watch plane did since the previous call, for a wave's
        record: `informer_relists` of this server's informers (full
        list+replace rounds; a healthy wave costs none) and, where the
        client reads the store's counters (`Client.local`: the store runs
        in this process), the store's `watch_evictions`, the largest lag of
        its pump, `pump_lag_max`, and what the pump cost: `pump_busy_s` (its
        thread's CPU seconds), `pump_turns`, `pump_events`. A store in
        another process leaves these out. `start()` resets it once the
        informers' initial lists are in."""
        now = sum(inf.relists for inf in (
            self.pod_informer, self.node_informer, self.pdb_informer,
            self.csinode_informer, self.sc_informer, self.pv_informer,
            self.pvc_informer) if inf is not None)
        out = {"informer_relists": now - self._relists_seen}
        self._relists_seen = now
        if self._store_counters is not None:
            out.update(self._store_counters())
        return out

    # -- lifecycle ----------------------------------------------------------- #

    def _on_pdb(self, obj: Obj) -> None:
        from kubernetes_tpu.api.v1 import _label_selector

        m = obj.get("metadata", {})
        key = f"{m.get('namespace', 'default')}/{m.get('name', '')}"
        self._pdb_cache[key] = (
            m.get("namespace", "default"),
            _label_selector(obj.get("spec", {}).get("selector")),
            int(obj.get("status", {}).get("disruptionsAllowed", 0)),
        )

    def _on_pdb_delete(self, obj: Obj) -> None:
        m = obj.get("metadata", {})
        self._pdb_cache.pop(
            f"{m.get('namespace', 'default')}/{m.get('name', '')}", None)

    def _start_volume_informers(self, tel) -> None:
        """The PersistentVolumeClaim, PersistentVolume, StorageClass and
        CSINode informers, listed and synced BEFORE the nodes and the pods
        (a node's limits and a bound pod's volumes are read off their
        listers as those arrive), and the volume binder over their listers:
        the stage `start/volumes-sync`, each list a stage below it."""
        from kubernetes_tpu.volume.binder import SchedulerVolumeBinder

        t0 = tel.clock()
        self.csinode_informer = SharedInformer(self.client.csinodes)
        self.csinode_informer.add_handlers(
            on_add=self._on_csinode,
            on_update=lambda old, new: self._on_csinode(new),
            on_delete=self._on_csinode)
        self.sc_informer = SharedInformer(self.client.storageclasses)
        self.pv_informer = SharedInformer(self.client.persistentvolumes)
        self.pvc_informer = SharedInformer(self.client.persistentvolumeclaims)
        for inf in (self.sc_informer, self.pv_informer):
            inf.add_handlers(
                on_add=self._on_volume_event,
                on_update=lambda old, new: self._on_volume_event(new))
        self.pvc_informer.add_handlers(
            on_add=self._on_claim,
            on_update=lambda old, new: self._on_claim(new))
        for name, inf in (("csinodes", self.csinode_informer),
                          ("storageclasses", self.sc_informer),
                          ("persistentvolumes", self.pv_informer),
                          ("persistentvolumeclaims", self.pvc_informer)):
            start_informer(inf, tel, f"start/volumes-sync/{name}",
                           "scheduler")
        tel.loop_span("start/volumes-sync", tel.clock() - t0)
        self.volume_binder = SchedulerVolumeBinder(
            self.client, self.pvc_informer.lister, self.pv_informer.lister,
            self.sc_informer.lister, self.node_informer.lister)
        self.scheduler.volume_binder = self.volume_binder
        if isinstance(self.scheduler.binder, APIBinder):
            self.scheduler.binder.volume_binder = self.volume_binder

    def _start_informers(self, tel) -> None:
        """Every informer of the start, each listed and synced in turn: the
        PDBs, the volumes' four, the nodes, the pods."""
        if self.scheduler.preemptor is not None \
                and getattr(self.scheduler.preemptor, "pdb_source", None) \
                is not None:
            self.pdb_informer = SharedInformer(
                self.client.poddisruptionbudgets)
            self.pdb_informer.add_handlers(
                on_add=self._on_pdb,
                on_update=lambda old, new: self._on_pdb(new),
                on_delete=self._on_pdb_delete)
            start_informer(self.pdb_informer, tel, "start/pdb-sync",
                           "scheduler")
        self.pod_informer = SharedInformer(self.client.pods)
        self.pod_informer.add_handlers(on_add=self._on_pod_add,
                                       on_update=self._on_pod_update,
                                       on_delete=self._on_pod_delete)
        self.node_informer = SharedInformer(self.client.nodes)
        self.node_informer.add_handlers(on_add=self._on_node_add,
                                        on_update=self._on_node_update,
                                        on_delete=self._on_node_delete)
        if self.volume_binding:
            self._start_volume_informers(tel)
        start_informer(self.node_informer, tel, "start/nodes-sync",
                       "scheduler")
        start_informer(self.pod_informer, tel, "start/pods-sync",
                       "scheduler")

    def start(self) -> "SchedulerServer":
        from kubernetes_tpu.utils.platform import (enable_compile_cache,
                                                   steady_heap)

        enable_compile_cache()  # before the loop's first compile
        steady_heap()  # before the first wave commits
        # the loop's account of the time between waves begins here: the
        # informers' list+sync below is the first wave's `start` phase,
        # and each stretch of it a stage below that (`loop.children`)
        tel = self.scheduler.telemetry
        tel.loop_reset()
        with initial_lists(tel, "scheduler"):
            self._start_informers(tel)
        self._watch_plane()  # the initial lists are no wave's relists
        if self.elector is not None:
            self.elector.start()
        # SIGUSR2 cache dump/compare (internal/cache/debugger/debugger.go:55)
        from kubernetes_tpu.sched.debugger import CacheComparer, install_sigusr2

        self.comparer = CacheComparer(self.scheduler.cache, self.client)
        install_sigusr2(self.comparer)
        # decision provenance (ISSUE 10): rich FailedScheduling events flow
        # through the apiserver on the APIBinder transport discipline (the
        # PR 8 retry budget) — wired here, where the informer lister can
        # supply involvedObject UIDs
        if self.scheduler.explainer is not None \
                and self.scheduler.explainer.sink is None:
            from kubernetes_tpu.sched.explain import APIEventSink

            self.scheduler.explainer.sink = APIEventSink(
                self.client, component=self.scheduler.scheduler_name,
                pod_lookup=lambda ns, name: (
                    self.pod_informer.lister.get(ns, name)
                    if self.pod_informer is not None else None))
        if self.telemetry_port is not None:
            self.telemetry_gateway = TelemetryGateway(
                self.scheduler.telemetry, port=self.telemetry_port,
                scheduler=self.scheduler).start()
        # the last stage, closed before the loop's thread exists: from its
        # first act (the `start` lap) on, the account is that thread's
        tel.loop_stage("start/wiring")
        t = threading.Thread(target=self._loop, daemon=True,
                             name="scheduler-loop")
        t.start()
        self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stop.set()
        if self.elector is not None:
            self.elector.stop()
        for inf in (self.pod_informer, self.node_informer,
                    self.pdb_informer, self.csinode_informer,
                    self.sc_informer, self.pv_informer, self.pvc_informer):
            if inf is not None:
                inf.stop()
        for t in self._threads:
            t.join(timeout=2)
        self._close_binder()
        self.recorder.stop(timeout=10)
        if self.telemetry_gateway is not None:
            self.telemetry_gateway.stop()
            self.telemetry_gateway = None
        self.scheduler.telemetry.stop_profile()

    def crash(self) -> None:
        """Simulated abrupt process death (restart drills, bench failover
        stage): the loop and informers stop, but the Lease is NOT released,
        no callbacks fire, and nothing is requeued or flushed — whatever
        the bind pipeline had in flight stays exactly where the 'kill'
        caught it (unretired intents included). The next leader's
        reconciliation is what cleans up — that is the thing under test."""
        self._crashed = True
        self._stop.set()
        if self.elector is not None:
            self.elector.crash()
        for inf in (self.pod_informer, self.node_informer,
                    self.pdb_informer, self.csinode_informer,
                    self.sc_informer, self.pv_informer, self.pvc_informer):
            if inf is not None:
                inf.stop()
        for t in self._threads:
            t.join(timeout=2)
        self._close_binder()
        self.recorder.abandon()

    def _close_binder(self) -> None:
        """The binder's threads live as long as the scheduler (APIBinder's
        window of Binding writes); the loop that hands them work is gone."""
        close = getattr(self.scheduler.binder, "close", None)
        if close is not None:
            close()

    def _on_stopped_leading(self) -> None:
        """Any leadership loss re-arms the reconciliation pass HERE, on the
        elector thread — not only in the loop's standby branch. A loop
        wedged inside a long degraded wave can lose and re-acquire the
        lease without ever observing the inactive state; arming on the
        callback guarantees the re-acquisition still replays whatever the
        interim leader left unretired before serving a single wave."""
        self._needs_recover = self.scheduler.ledger is not None
        self._active.clear()

    def _lookup_pod(self, pod_key: str):
        """Informer truth for intent replay: the live pod (node_name = the
        apiserver's committed view) or None when deleted."""
        from kubernetes_tpu.api.v1 import pod_from_v1

        ns, name = meta.split_key(pod_key)
        obj = self.pod_informer.lister.get(ns, name) \
            if self.pod_informer is not None else None
        if obj is None:
            return None
        return self._to_pod(obj) if not obj.get("spec", {}).get("nodeName") \
            else pod_from_v1(obj)

    # -- the loop (wait.Until(scheduleOne) → batched waves) ------------------ #

    def _loop(self) -> None:
        from kubernetes_tpu.utils.platform import steady_heap

        steady_heap()  # this thread commits the waves: room in ITS arena
        # every second between two waves that attempted pods goes to one
        # named stretch (telemetry.loop_lap) and rides the later wave's
        # flight-recorder record as `loop`: where the chip sat idle
        lap = self.scheduler.telemetry.loop_lap
        lap("start")
        while not self._stop.is_set():
            if not self._active.is_set():
                # warm standby: the next activation must find compiled
                # executables and a resident snapshot, not a cold encoder —
                # failover skips cold-compile and full re-ingest
                self._needs_recover = self.scheduler.ledger is not None
                now = time.monotonic()
                if now - self._standby_last >= self.standby_warm_interval:
                    self._standby_last = now
                    with self._mu:
                        try:
                            self.scheduler.warm_standby()
                        except Exception:  # noqa: BLE001 - standby warmth
                            pass           # is an optimization, never fatal
                self._stop.wait(0.2)
                lap("standby")
                continue
            if self._needs_recover:
                # first led beat (process start, or a takeover): replay
                # unretired bind intents against informer truth before any
                # wave pops a pod — exactly-once binding across the handoff
                self._needs_recover = False
                with self._mu:
                    try:
                        self.last_recovery = self.scheduler.recover(
                            lookup=self._lookup_pod)
                        self.takeovers += 1
                        # a takeover is a flight-recorder trigger: the ring
                        # at this moment explains what the interim leader's
                        # waves looked like when the lease changed hands
                        self.scheduler.telemetry.dump("takeover")
                    except Exception as e:  # noqa: BLE001 - a failed
                        # recovery pass leaves the intents unretired for
                        # the next one; scheduling proceeds (pods are
                        # requeued by informer truth regardless)
                        self.last_recovery_error = e
                lap("recover")
            self._gather(lap)
            stats = self.run_one_wave()
            if stats is None or stats.attempted == 0:
                self._stop.wait(self.cycle_interval)
                lap("idle-wait")  # the active queue was empty

    def _peek(self, lap) -> tuple:
        """(activeQ depth, the instant its oldest entry has waited from,
        whether pod events that have reached the scheduler are not on the
        queue yet: a handler stands at `_mu`, or the pod informer's one
        thread has events in its buffer behind it), read under `_mu`."""
        with self._mu:
            lap("lock-wait")  # behind the informer handlers
            pending, oldest = self.scheduler.queue.active_stats()
            inf = self.pod_informer
            behind = bool(self._handlers_waiting) \
                or (inf is not None and inf.buffered() > 0)
            if behind:
                self._handlers_through.clear()
        return pending, oldest, behind

    def _window(self, pending: int) -> float:
        """How long pending pods are given to gather into one wave:
        coalesce STORMS into few large waves with the full window; a small
        pending set (a preemption retry burst, a gang trickling in over
        milliseconds) gets a SHORT one — enough to gather co-created pods
        into one all-or-nothing wave, without the full window's latency
        tax on every tiny wave (the r5 preempt burst spent ~1 s just
        waiting)."""
        return self.batch_window if pending >= 32 \
            else min(0.05, self.batch_window)

    def _gather(self, lap) -> None:
        """When the next wave starts. The window (`_window`) is counted
        from the instant the OLDEST pod now in activeQ reached the
        scheduler (a requeued one: came back), not from this peek: a pod
        created during a wave has waited out the wave behind `_mu`, and so
        have the pods created with it; the loop sleeps only what is left
        of the window (`batch-wait`), and not at all where none is. Pod
        events that reached the scheduler before the peek (`_peek`) belong
        in the wave: `_mu` is not fair and the loop would win it back from
        their handlers, so the loop lets those go first, for at most a
        window from its first peek (`lock-wait`: the loop behind the
        handlers). The age of the oldest pod and the sleep taken ride the
        wave's record (`gather_age_s`, `gather_wait_s`)."""
        sched = self.scheduler
        pending, oldest, behind = self._peek(lap)
        if behind and self.batch_window:
            until = sched.clock() + self._window(pending)
            while behind and not self._stop.is_set():
                left = until - sched.clock()
                if left <= 0.0:
                    break
                self._handlers_through.wait(left)
                pending, oldest, behind = self._peek(lap)
        age = wait = 0.0
        if pending and self.batch_window:
            age = max(sched.clock() - oldest, 0.0)
            wait = max(self._window(pending) - age, 0.0)
            if wait:
                self._stop.wait(wait)  # let the batch fill
                lap("batch-wait")
        sched.telemetry.note_gather(age, wait)

    def run_one_wave(self):
        from kubernetes_tpu.sched import metrics as sched_metrics

        lap = self.scheduler.telemetry.loop_lap
        with self._mu:
            lap("lock-wait")
            try:
                stats = self.scheduler.schedule_pending()
            except Exception as e:  # noqa: BLE001 — the loop never dies
                self.wave_errors += 1
                self.last_wave_error = e
                return None
            # depths() carries the deferred lane too — the governor's own
            # control signals become scrapeable gauges
            queue_lengths = self.scheduler.queue.depths()
            cache_counts = self.scheduler.cache.counts()[:2]
        sched_metrics.observe_wave(stats, queue_lengths, cache_counts)
        self.total_scheduled += stats.scheduled
        if stats.unschedulable:
            self.total_unschedulable_events += stats.unschedulable
        # FailedScheduling events, as scheduler.go:436-448 records on
        # FitError: queued here (the involved object resolved now, while
        # the pod is known to exist), written by the recorder's sink
        # thread. With decision provenance on, the explainer already
        # emitted the rich per-predicate events from inside the wave for
        # every pod it ATTRIBUTED — the generic message would double-post
        # a weaker duplicate for those. But failure paths the attribution
        # never sees (extender rejections, framework rollbacks, a failed
        # attribution readback) must still get the generic event: gate per
        # pod on whether an attribution doc exists, not on the explainer's
        # mere presence.
        # A member of a refused gang gets its group's verdict in place of
        # the generic message: which group, and the members pending or
        # fitting against those still needed.
        explainer = self.scheduler.explainer
        failed: dict = {}   # message -> the pods it is said of
        for key in stats.failed_keys:
            if explainer is not None and explainer.why(key) is not None:
                continue
            msg = stats.gang_refusals.get(key) \
                or stats.volume_waits.get(key) \
                or "no nodes available to schedule pod"
            ns, name = meta.split_key(key)
            obj = self.pod_informer.lister.get(ns, name) \
                if self.pod_informer else None
            if obj is not None:
                failed.setdefault(msg, []).append(obj)
        for msg, objs in failed.items():
            self.recorder.events(objs, "Warning", "FailedScheduling", msg)
        # an empty call (no pod popped) is part of the wait for pods
        lap("post-wave" if stats.attempted else "idle-wait")
        return stats

    def wait_until_idle(self, timeout: float = 30.0) -> bool:
        """Test helper: wait until no pods are pending in the active queue."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._mu:
                active = self.scheduler.queue.lengths()[0]
            if active == 0:
                return True
            time.sleep(0.05)
        return False
