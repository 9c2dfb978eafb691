"""`extender_loop`: the closed loop of a stock kube-scheduler whose Policy
delegates Filter, Prioritize and Bind to ONE Scheduler Extender and runs no
predicate or priority of its own (upstream `scheduleOne`, one pod at a time:
`core/extender.go` `Filter` :289, `Prioritize` :355, `Bind` :397, `send`
:424-450). `StandIn` is that scheduler's stand-in: stdlib HTTP only, nothing
of the program imported; the wiring (`wirings/extender.py`) hands it the
client it lists and watches through and the extender's URL.

ONE thread. For each pod without a node, in creation order: POST `filter`
{Pod, NodeNames: every node}; POST `prioritize` {Pod, NodeNames: the
survivors}; the highest score, ties by a seeded draw; POST `bind`, awaited;
next. A call over `httpTimeout`, a non-200 or an `Error` is counted
(`extender_call_errors`) and the pod retried once after the queue's end. The
host clock is read round each POST; the rest of a pod's turn (JSON both ways,
the choice) is the stand-in's own time and is reported too
(`standin_self_ms_per_pod`), so the harness cannot hide in the result.

`Kind` is `backlog` over a BOUND cluster: the configuration's `existing_pods`
are bound, its `backlog_pods` wait at the apiserver, and the measured served
extender and its stand-in start over warm executables when the window opens
(a failover with pods pending); the window closes at the last Binding on the
client's watch. Of every 10th pod the measured stand-in keeps the `filter`
answer for `checks/extender_answers.py` (module-level `KEPT`: a check is
handed nothing of the run but the configuration).
"""

from __future__ import annotations

import collections
import gc
import http.client
import json
import random
import threading
import time
import urllib.parse

from ..probes import log
from . import backlog

UNASSIGNED = "spec.nodeName="
MAX_EXTENDER_PRIORITY = 10   # apis/extender/v1/types.go:29


class Kept:
    """What the measured stand-in keeps for the checks: of every
    `every`-th pod the last `filter` answer it got, and each `prioritize`
    answer that was malformed."""

    def __init__(self):
        self.reset()

    def reset(self, every: int = 0) -> None:
        self.every = every
        self.filters: dict = {}     # pod name -> (asked, passed, failed)
        self.malformed: list = []


KEPT = Kept()


class CallError(Exception):
    """A verb's call timed out, was not a 200, or answered an `Error`."""


class StandIn:
    def __init__(self, client, policy: dict):
        self.client, self.policy = client, policy
        self.timeout = float(policy["httpTimeout_s"])
        self.reseed(0, None)   # the warm-up's stand-in keeps nothing
        self.queue: collections.deque = collections.deque()
        self.queued: set = set()       # UIDs ever queued
        self.retry: list = []
        self.retried: set = set()
        self.gave_up: list = []        # errors on the second attempt too
        self.unschedulable: list = []  # `filter` left no node
        self.errors: list = []         # (verb, pod, what)
        self.loop_errors: list = []
        self.turns = 0
        # host clock, ms, one sample a pod that went through all three verbs
        self.call_ms = {"filter": [], "prioritize": [], "bind": []}
        self.self_ms: list = []
        self.turn_log: list = []       # (ms, pod, {verb: ms}), every turn
        self._in_turn = False
        self._stop = threading.Event()
        self._thread = None
        self._watch = None

    def reseed(self, seed: int, kept) -> None:
        """Before `start()`: the run's seed for the tie draw, and where the
        measured window's answers are kept (a `Kept`, or None)."""
        self.rng = random.Random(seed * 1_000_003 + 41)
        self.kept = kept

    # -- lifecycle ---------------------------------------------------------- #

    def start(self, url: str) -> "StandIn":
        u = urllib.parse.urlsplit(url)
        self.host, self.port, self.prefix = u.hostname, u.port, u.path
        # the stock scheduler's node informer: the names it passes with
        # every `filter` (nodeCacheCapable: names only)
        self.node_names = [n["metadata"]["name"]
                           for n in self.client.nodes.list()["items"]]
        self._open()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-standin-scheduler")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._watch is not None:
            self._watch.stop()
        if self._thread is not None:
            self._thread.join(timeout=self.timeout + 5)

    def depths(self) -> dict:
        """The stand-in's queue in the words `probes.settled` reads."""
        return {"active": len(self.queue) + int(self._in_turn),
                "backoff": len(self.retry),
                "unschedulable": len(self.gave_up) + len(self.unschedulable),
                "deferred": 0}

    # -- the queue: pods without a node, in creation order ------------------ #

    def _open(self) -> None:
        listing = self.client.pods.list("", field_selector=UNASSIGNED)
        waiting = sorted(listing.get("items", ()), key=lambda p: int(
            p["metadata"].get("resourceVersion") or 0))
        for p in waiting:
            self._enqueue(p)
        self._watch = self.client.pods.watch(
            "", field_selector=UNASSIGNED,
            resource_version=listing["metadata"]["resourceVersion"])

    def _enqueue(self, pod: dict) -> None:
        uid = pod["metadata"]["uid"]
        if uid not in self.queued:
            self.queued.add(uid)
            self.queue.append(pod)

    def _pump(self, wait: float) -> None:
        """Take what the watch has; block up to `wait` for the first."""
        while not self._stop.is_set():
            ev = self._watch.next(timeout=wait)
            if ev is None:
                if self._watch.stopped and not self._stop.is_set():
                    self._open()
                return
            wait = 0.0
            if ev.type == "ERROR":
                self._open()
            elif ev.type == "ADDED" and not (
                    ev.object.get("spec") or {}).get("nodeName"):
                self._enqueue(ev.object)

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self._pump(0.0 if self.queue or self.retry else 0.05)
                if not self.queue and self.retry:
                    # the queue's end: each failed pod once more
                    self.queue.extend(self.retry)
                    self.retry.clear()
                if self.queue:
                    self._in_turn = True
                    self._schedule_one(self.queue.popleft())
                    self._in_turn = False
            except Exception as e:  # noqa: BLE001 - counted, the loop lives
                self._in_turn = False
                self.loop_errors.append(repr(e)[:300])
                time.sleep(0.05)

    # -- scheduleOne -------------------------------------------------------- #

    def _post(self, verb: str, body: dict):
        """send() (extender.go:424-450): POST JSON, decode JSON. Returns
        (the decoded answer, the milliseconds the POST took)."""
        data = json.dumps(body).encode()
        t0 = time.perf_counter()
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout)
        try:
            conn.request("POST", f"{self.prefix}/{self.policy[verb + 'Verb']}",
                         body=data,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            raw = resp.read()
        except OSError as e:   # a timeout among them
            raise CallError(f"{type(e).__name__}: {e}") from e
        finally:
            conn.close()
        ms = (time.perf_counter() - t0) * 1000.0
        if resp.status != 200:
            raise CallError(f"HTTP {resp.status}: {raw[:200]!r}")
        return json.loads(raw), ms

    def _schedule_one(self, pod: dict) -> None:
        pc = time.perf_counter
        t_turn = pc()
        meta = pod["metadata"]
        name, verb, ms = meta["name"], "filter", {}
        self.turns += 1
        try:
            asked = self.node_names
            flt, ms["filter"] = self._post("filter", {
                "Pod": pod, "Nodes": None, "NodeNames": asked})
            if flt.get("Error"):
                raise CallError(flt["Error"])
            passed = flt.get("NodeNames") or []
            if self.kept is not None and self.kept.every \
                    and self.turns % self.kept.every == 0:
                self.kept.filters[name] = (asked, passed,
                                           flt.get("FailedNodes") or {})
            if not passed:
                # upstream would go on to preemption; this backlog fits
                self.unschedulable.append(name)
                return
            verb = "prioritize"
            prio, ms["prioritize"] = self._post("prioritize", {
                "Pod": pod, "Nodes": None, "NodeNames": passed})
            host = self._select_host(name, passed, prio)
            verb = "bind"
            res, ms["bind"] = self._post("bind", {
                "PodName": name, "PodNamespace": meta["namespace"],
                "PodUID": meta["uid"], "Node": host})
            if res.get("Error"):
                raise CallError(res["Error"])
        except CallError as e:
            self.errors.append((verb, name, str(e)[:200]))
            if name in self.retried:
                self.gave_up.append(name)
            else:
                self.retried.add(name)
                self.retry.append(pod)
            return
        for v, took in ms.items():
            self.call_ms[v].append(took)
        turn_ms = (pc() - t_turn) * 1000.0
        self.self_ms.append(turn_ms - sum(ms.values()))
        self.turn_log.append((round(turn_ms, 1), name,
                              {v: round(took, 1) for v, took in ms.items()}))

    def _select_host(self, name: str, passed: list, prio) -> str:
        """selectHost: the highest score, ties by a seeded draw (upstream:
        reservoir sampling). On the way every answer is held to its form:
        each candidate scored exactly once, no host that was not asked, each
        score a whole number from 0 to MaxExtenderPriority."""
        best, ties, seen, bad, asked = -1, [], set(), "", set(passed)
        for entry in prio if isinstance(prio, list) else ():
            host, score = entry.get("Host"), entry.get("Score")
            if host in seen:
                bad = bad or f"{host} scored twice"
                continue
            seen.add(host)
            if host not in asked:
                continue
            if not isinstance(score, int) \
                    or not 0 <= score <= MAX_EXTENDER_PRIORITY:
                bad = bad or f"{host} scored {score!r}"
            elif score > best:
                best, ties = score, [host]
            elif score == best:
                ties.append(host)
        if not bad and seen != asked:
            bad = (f"{len(seen - asked)} hosts not asked, "
                   f"{len(asked - seen)} candidates unscored")
        if bad and self.kept is not None:
            self.kept.malformed.append(f"prioritize {name}: {bad}")
        if not ties:
            raise CallError("prioritize scored no candidate")
        return ties[self.rng.randrange(len(ties))]


class Kind(backlog.Kind):   # nothing is deleted: `check_spread` stays true

    def __init__(self, tr: dict, cfg: dict, seconds: float):
        self.cfg = cfg
        self.prebound = self.work = cfg["existing_pods"]
        KEPT.reset(every=10)

    def prepare(self, cluster, server, watch, shapes, seed: int) -> tuple:
        # as `backlog`: the measured extender and its stand-in are a new
        # process's worth of state over the warm executables (a failover
        # with pods pending); the stand-in draws with the run's seed
        server, pods = super().prepare(cluster, server, watch, shapes, seed)
        server.standin.reseed(seed, KEPT)
        return server, pods

    def open(self, t0: float, series: dict) -> None:
        # full collections of the interpreter's collector stop every thread:
        # how many fell into the window, and how long, goes to the log
        self.full_gcs: list = []
        self._gc_t = 0.0

        def on_gc(phase, info):
            if info["generation"] == 2:
                if phase == "start":
                    self._gc_t = time.perf_counter()
                else:
                    self.full_gcs.append(time.perf_counter() - self._gc_t)

        self._on_gc = on_gc
        gc.callbacks.append(on_gc)
        super().open(t0, series)

    def close(self) -> bool:
        gc.callbacks.remove(self._on_gc)
        return super().close()

    def results(self, series: dict, names: list, seen: dict, final: dict,
                in_window: dict, t0: float, window_end: float,
                waves: list) -> tuple:
        standin = self.server.standin
        log(f"window: {len(self.full_gcs)} full collections, "
            f"{sum(self.full_gcs):.2f}s in all, the longest "
            f"{max(self.full_gcs, default=0.0):.2f}s; the slowest turns: "
            f"{sorted(standin.turn_log, reverse=True)[:3]}")
        for verb, samples in standin.call_ms.items():
            series[f"extender_{verb}_call_ms"] = samples or None
        series["standin_self_ms"] = standin.self_ms or None
        for verb, name, what in standin.errors[:5]:
            log(f"extender call error: {verb} {name}: {what}")
        return super().results(series, names, seen, final, in_window, t0,
                               window_end, waves)
