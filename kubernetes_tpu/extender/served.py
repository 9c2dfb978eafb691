"""The extender as an operator starts it: one object over a `Client`.

`ExtenderBackend` answers the verbs from a mirror; this module gives the
mirror its feed and the backend its Binding write, as `SchedulerServer`
(sched/server.py) does for the scheduler:

  * node and pod informers over the client feed `backend.cache` one event at
    a time (initial list included); their relists ride the pod's record;
  * `bind` goes through `APIBinder(client)` (POST pods/{name}/binding with
    the retry budget) and assumes the pod first (backend.py `_bind`);
  * the verbs' one program and the patch-scatter ladder are run once at
    `start()`, after the initial lists are in and before the socket opens,
    so that the first request answers inside upstream's `httpTimeout` (5 s
    by default; a non-ignorable extender that misses it fails the pod);
  * `start()` is accounted as `SchedulerServer.start()` is, on the
    backend's telemetry: a `start` lap with its stages below it, on the
    first pod's record (`loop`, `loop.children`).

Point a stock kube-scheduler's Policy at `.url` (server.py has the entry).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from ..api.types import Pod
from ..api.v1 import node_from_v1, pod_from_v1
from ..client.informers import SharedInformer
from ..component import trace
from ..machinery import meta
from ..sched.server import (
    APIBinder,
    decoded,
    initial_lists,
    pod_schedulable_v1,
    start_informer,
)
from ..state.dims import Dims
from .backend import ExtenderBackend
from .server import ExtenderServer

Obj = Dict[str, Any]


class ServedExtender:
    """Informers -> mirror -> verbs over HTTP -> Bindings through the API."""

    def __init__(self, client, base_dims: Optional[Dims] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 url_prefix: str = "/scheduler",
                 verbs: Optional[dict] = None,
                 managed_resources: Sequence[str] = ()) -> None:
        self.client = client
        self.binder = APIBinder(client)
        self.backend = ExtenderBackend(
            base_dims=base_dims, managed_resources=managed_resources,
            binder=self.binder.bind, pod_lookup=self._lookup_pod)
        self.backend.watch_plane = self._watch_plane
        self.http = ExtenderServer(self.backend, host=host, port=port,
                                   url_prefix=url_prefix, verbs=verbs)
        self.pod_informer: Optional[SharedInformer] = None
        self.node_informer: Optional[SharedInformer] = None
        self._relists_seen = 0
        # the store's side of the watch plane, where the client can read it
        # (`Client.local`); None over HTTP
        counters = getattr(client, "store_counters", None)
        self._store_counters = counters() if counters is not None else None
        #: [(Dims, program)] that `start()` ran ahead of the first request
        self.warm_log: list = []
        # the start's account as `start()` closed it (telemetry.loop_account)
        self._start_account: Dict[str, list] = {}

    @property
    def url(self) -> str:
        return self.http.url

    @property
    def start_log(self) -> list:
        """[(stretch, seconds)] of `start()`, read off its account (the
        `start/*` stages of the first pod's `loop.children`): the two
        initial lists as the handlers fed them to the mirror, the
        compile-ahead, the socket. Empty with telemetry off."""
        return [(path.split("/")[1], round(v[1], 6))
                for path, v in self._start_account.items()
                if path.count("/") == 1]

    # -- the mirror's feed --------------------------------------------------- #

    def _on_pod(self, obj: Obj) -> None:
        # inside the informer's first list the conversion is the round's
        # `handlers/decode` (`decoded`); the rest of `handlers` is the mirror
        self.backend.observe_pod(decoded(pod_from_v1, obj),
                                 live=pod_schedulable_v1(obj))

    def _on_pod_delete(self, obj: Obj) -> None:
        self.backend.forget_pod(meta.namespaced_key(obj))

    def _on_node(self, obj: Obj) -> None:
        self.backend.observe_node(decoded(node_from_v1, obj))

    def _lookup_pod(self, namespace: str, name: str) -> Optional[Pod]:
        obj = self.pod_informer.lister.get(namespace, name) \
            if self.pod_informer is not None else None
        return pod_from_v1(obj) if obj is not None else None

    def _watch_plane(self) -> Dict[str, Any]:
        """What the watch plane did since the previous pod's record, as
        `SchedulerServer._watch_plane` counts it for a wave's."""
        now = sum(inf.relists for inf in (self.pod_informer,
                                          self.node_informer)
                  if inf is not None)
        out = {"informer_relists": now - self._relists_seen}
        self._relists_seen = now
        if self._store_counters is not None:
            out.update(self._store_counters())
        return out

    # -- lifecycle ----------------------------------------------------------- #

    def start(self) -> "ServedExtender":
        from ..utils.platform import enable_compile_cache, steady_heap

        enable_compile_cache()  # before the compile-ahead
        steady_heap()
        self.backend.telemetry.loop_reset()
        self.node_informer = SharedInformer(self.client.nodes)
        self.node_informer.add_handlers(
            on_add=self._on_node,
            on_update=lambda old, new: self._on_node(new),
            on_delete=lambda obj: self.backend.forget_node(meta.name(obj)))
        self.pod_informer = SharedInformer(self.client.pods)
        self.pod_informer.add_handlers(
            on_add=self._on_pod,
            on_update=lambda old, new: self._on_pod(new),
            on_delete=self._on_pod_delete)
        tel = self.backend.telemetry
        # the mirror's first full snapshot (the compile-ahead's `prepare`)
        # lives as long as the mirror: it is built with the lists
        with initial_lists(tel, "extender"):
            start_informer(self.node_informer, tel, "start/nodes-sync",
                           "extender")
            start_informer(self.pod_informer, tel, "start/pods-sync",
                           "extender")
            self._watch_plane()  # the initial lists are no pod's relists
            # the compile-ahead files each program it runs on
            # `trace.current()` (None with telemetry off)
            ahead = trace.Trace("compile-ahead", clock=tel.clock)
            token = trace.activate(ahead if tel.enabled else None)
            try:
                self.warm_log = self.backend.compile_ahead()
            finally:
                trace.deactivate(token)
            tel.loop_stage("start/compile-ahead", below=ahead.record())
        self.http.start()
        tel.loop_stage("start/socket")
        tel.loop_lap("start")
        self._start_account = tel.loop_account()
        return self

    def stop(self) -> None:
        self.http.stop()
        for inf in (self.pod_informer, self.node_informer):
            if inf is not None:
                inf.stop()
        self.backend.flush_record()

    def __enter__(self) -> "ServedExtender":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
