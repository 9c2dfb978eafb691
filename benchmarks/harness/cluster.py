"""The system under test, wired as a deployment wires it, and the client's
view of it. This is the only module of the benchmark that imports the
program: APIServer + native store, Client.local, SchedulerServer over a
Scheduler sized from the configuration (APIBinder, API preemptor, bind-intent
ledger), its flight recorder and counters. Copied from chip_smoke.py's
serving leg (proved on the chip in PR 21), which later PRs may change.
"""

from __future__ import annotations

import sys
import threading
import time

#: supervisor counters that must all end 0 (sched/supervisor.py)
SUPERVISOR_ZERO = ("fallback_dispatches", "degraded_cycles",
                   "watchdog_timeouts", "device_errors", "abandoned",
                   "compile_failures")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


_T_IMPORT = time.perf_counter()


def log(msg: str) -> None:
    """Progress, on stderr, stamped with the seconds since the harness was
    imported (set-up is most of a run: the stamps say where it goes)."""
    print(f"# [{time.perf_counter() - _T_IMPORT:6.1f}] {msg}",
          file=sys.stderr, flush=True)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed place: where
    JAX_COMPILATION_CACHE_DIR is set, there; else <checkout>/.cache/xla, the
    same directory the program's own enable_compile_cache() picks, so the two
    never disagree."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        d = os.path.join(root, ".cache", "xla")
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return jax.config.jax_compilation_cache_dir


class CompileCounter:
    """Counts XLA compilations and executable loads (both pass through
    jax's backend-compile event) from `arm()` on."""

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == COMPILE_EVENT:
            self.events.append((kw.get("fun_name", "?"), round(duration, 4)))

    def arm(self) -> None:
        self.armed = True

    def disarm(self) -> None:
        self.armed = False


def serving_dims(cfg: dict):
    """Capacities provisioned for the whole run from the configuration's own
    numbers, so no cycle crosses a bucket and recompiles (chip_smoke.py
    serving_dims): P for the configuration's whole published backlog, E
    through grown_for (the bound-pod axis doubles, state/dims.py)."""
    from kubernetes_tpu.state.dims import Dims, bucket

    return Dims(N=bucket(cfg["nodes"]), D=bucket(cfg["nodes"]),
                P=bucket(cfg["batch_pods"]), SC=64, SL=64).grown_for(
                    E=cfg["existing_capacity_pods"])


class BindWatch:
    """The client's own list+watch on pods: when each pod was first seen with
    a node, whether any was ever seen on two, and the order in which Bindings
    and deletions arrived (what the reference replays). A watch the apiserver
    closes (a deaf consumer's eviction) is re-opened from a fresh list, as a
    real client does, and counted."""

    def __init__(self, client):
        self.client = client
        self.bound: dict = {}
        self.t_bound: dict = {}
        self.rebound: list = []
        self.history: list = []
        self.restarts = -1
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self._open()
        self._thread = threading.Thread(target=self._pump, daemon=True,
                                        name="bench-bind-watch")
        self._thread.start()

    def _open(self) -> None:
        listing = self.client.pods.list("default")
        now = time.perf_counter()
        names = set()
        for obj in listing.get("items", ()):
            names.add(obj["metadata"]["name"])
            self._note(obj, now)
        with self._mu:
            for gone in [n for n in self.bound if n not in names]:
                self.history.append(("deleted", gone, ""))
        self._watch = self.client.pods.watch(
            "default",
            resource_version=listing["metadata"]["resourceVersion"])
        self.restarts += 1

    def _note(self, obj: dict, now: float) -> None:
        node = (obj.get("spec") or {}).get("nodeName")
        if not node:
            return
        name = obj["metadata"]["name"]
        with self._mu:
            prev = self.bound.get(name)
            if prev is None:
                self.bound[name] = node
                self.t_bound[name] = now
                self.history.append(("bound", name, node))
            elif prev != node:
                self.rebound.append((name, prev, node))

    def _pump(self) -> None:
        while not self._stop.is_set():
            ev = self._watch.next(timeout=0.5)
            if ev is None:
                if self._watch.stopped and not self._stop.is_set():
                    self._open()
                continue
            now = time.perf_counter()
            if ev.type == "ERROR":
                self._open()
            elif ev.type == "DELETED":
                with self._mu:
                    self.history.append(
                        ("deleted", ev.object["metadata"]["name"], ""))
            elif ev.type in ("ADDED", "MODIFIED"):
                self._note(ev.object, now)

    def count_bound(self, names) -> int:
        with self._mu:
            return sum(1 for n in names if n in self.bound)

    def snapshot(self) -> dict:
        with self._mu:
            return {"bound": dict(self.bound), "t_bound": dict(self.t_bound),
                    "rebound": list(self.rebound),
                    "history": list(self.history)}

    def stop(self) -> None:
        self._stop.set()
        self._watch.stop()
        self._thread.join(timeout=5)


class WaveLog:
    """The flight recorder's wave records, merged by sequence number on every
    poll so none is lost when the bounded ring wraps."""

    def __init__(self, scheduler):
        self.scheduler = scheduler
        self.by_seq: dict = {}

    def poll(self) -> None:
        for r in self.scheduler.telemetry.recorder.records():
            self.by_seq.setdefault(r["seq"], r)

    def waves(self, t0: float, t1: float) -> list:
        """Every wave that attempted a pod and started inside [t0, t1), in
        order. `t_start` is on time.perf_counter's clock, as the harness's
        own instants are."""
        self.poll()
        return [r for _s, r in sorted(self.by_seq.items())
                if (r.get("stats") or {}).get("attempted")
                and t0 <= r["t_start"] < t1]


class Cluster:
    """One apiserver, one client, and the scheduler servers run against them.
    `new_server()` builds a scheduler process; `adopt_warmth()` hands a
    stopped warm-up server's compiled executables to the next one."""

    def __init__(self, cfg: dict):
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.client import Client

        self.cfg = cfg
        self.api = APIServer()
        self.client = Client.local(self.api)
        self.kvstore = type(self.api.storage.kv).__name__
        self.dims = serving_dims(cfg)
        self.servers: list = []

    def new_server(self):
        from kubernetes_tpu.sched.ledger import BindIntentLedger
        from kubernetes_tpu.sched.preemption import APIEvictor, Preemptor
        from kubernetes_tpu.sched.scheduler import Scheduler
        from kubernetes_tpu.sched.server import APIBinder, SchedulerServer

        sched = Scheduler(binder=APIBinder(self.client),
                          batch_size=self.dims.P, base_dims=self.dims)
        if self.cfg["preemption"]:
            # victims are evicted through the API, as SchedulerServer wires it
            sched.preemptor = Preemptor(evictor=APIEvictor(self.client))
        server = SchedulerServer(
            self.client, scheduler=sched,
            cycle_interval=self.cfg["assumed"]["cycle_interval_s"],
            batch_window=self.cfg["assumed"]["batch_window_s"],
            ledger=BindIntentLedger(self.api.storage, identity="bench")
            if self.cfg["bind_intent_ledger"] else None)
        self.servers.append(server)
        return server

    @staticmethod
    def adopt_warmth(warm, fresh) -> None:
        """A failover lands on a process whose executables are loaded: give
        the fresh scheduler the warm one's prewarmer (AOT executables and the
        record of what compile-ahead already ran). Cluster state, queue,
        encoder and snapshot stay cold."""
        pw = warm.scheduler.prewarmer
        pw.wait(600)
        fresh.scheduler.prewarmer = pw
        fresh.scheduler.supervisor.prewarmer = pw
        pw.supervisor = fresh.scheduler.supervisor

    @staticmethod
    def _resident_snapshot(server):
        """The scheduler's resident planes as the next wave would see them
        (no pending pods), taken under the server's own lock."""
        from kubernetes_tpu.sched.cycle import snapshot_with_keys

        sched = server.scheduler
        with server._mu:
            snap, _keys = snapshot_with_keys(
                sched.cache, sched.encoder, [], sched.base_dims,
                mesh=sched.supervisor.snapshot_mesh())
        return snap

    def warm_patch_ladder(self, server) -> int:
        """Compile the patch-scatter ladder for this server's resident planes
        (state/cache.py warm_patch_ladder), so no wave of the window meets a
        new rung."""
        return server.scheduler.cache.warm_patch_ladder(
            self._resident_snapshot(server))

    def counters(self, server) -> dict:
        """Every counter that must read zero, and the informers' relists."""
        from kubernetes_tpu.client.informers import INFORMER_RELISTS

        sched = server.scheduler
        stats = sched.supervisor.stats
        out = {k: int(getattr(stats, k)) for k in SUPERVISOR_ZERO}
        out["supervisor_unhealthy"] = 0 if sched.supervisor.healthy else 1
        out["wave_errors"] = int(server.wave_errors)
        out["prewarm_type_error_drops"] = int(
            sched.prewarmer.type_error_drops)
        out["intents_unretired"] = len(sched.ledger.unretired()) \
            if sched.ledger is not None else 0
        info = {"informer_relists": int(INFORMER_RELISTS.total()),
                "prewarm_hits": int(sched.prewarmer.hits),
                "last_wave_error": repr(server.last_wave_error)
                if server.last_wave_error else None,
                "supervisor_last_failure": stats.last_failure}
        return {"zero": out, "info": info}

    def array_platforms(self, server) -> list:
        import jax

        snap = self._resident_snapshot(server)
        return sorted({d.platform
                       for a in jax.tree.leaves((snap.tables, snap.existing))
                       for d in a.devices()})

    def close(self) -> None:
        for s in self.servers:
            s.stop()
        self.api.close()


def wait_until(cond, timeout: float, interval: float = 0.05) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def settled(server, unbound) -> bool:
    """Nothing left the scheduler could act on: active and backoff lanes
    empty, every still-unbound pod parked as unschedulable or deferred."""
    d = server.scheduler.queue.depths()
    return not d["active"] and not d["backoff"] \
        and unbound() == d["unschedulable"] + d["deferred"]
