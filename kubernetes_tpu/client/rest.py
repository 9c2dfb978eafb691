"""REST transports + the typed client surface.

Analog of client-go's rest.RESTClient + typed clientsets. Two transports
serve the same interface: `LocalTransport` calls the in-process engine
directly (the integration-test path), `HTTPTransport` crosses the real wire
with chunked watch streams. Components depend only on `Client`.
"""

from __future__ import annotations

import http.client
import json
import random
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

from kubernetes_tpu.component import trace
from kubernetes_tpu.machinery import errors, meta
from kubernetes_tpu.machinery import watch as mwatch

Obj = Dict[str, Any]


@dataclass
class RetryPolicy:
    """Client-side retry budget for server PUSHBACK (ISSUE 9): 429 from
    the apiserver's max-inflight filter and 503 from a restart window are
    rejected BEFORE the request mutates anything, so retrying them is
    safe for every verb. Capped exponential backoff with jitter; the
    Status' `retryAfterSeconds` (the wire form of the reference's
    `Retry-After: 1` header) is honored as a floor; `deadline_s` bounds
    the whole attempt train. Any other failure propagates immediately."""

    attempts: int = 3          # retries after the first try
    base_s: float = 0.05
    cap_s: float = 1.0
    deadline_s: float = 5.0
    # observability hook: called once per retry actually taken (APIBinder
    # counts absorbed pushback through it)
    on_retry: Optional[Any] = None

    def run(self, fn):
        deadline = time.monotonic() + self.deadline_s
        delay = self.base_s
        for attempt in range(self.attempts + 1):
            try:
                return fn()
            except errors.StatusError as e:
                if e.code not in (429, 503) or attempt >= self.attempts:
                    raise
                ra = float((e.details or {}).get("retryAfterSeconds") or 0)
                wait = max(ra, delay * random.uniform(0.5, 1.0))
                if time.monotonic() + wait > deadline:
                    raise
                if self.on_retry is not None:
                    self.on_retry()
                time.sleep(wait)
                delay = min(delay * 2, self.cap_s)
        raise AssertionError("unreachable")  # loop always returns/raises


#: Binding writes an `HTTPTransport` lets a wave keep in flight at once, each
#: on a kept-alive connection of its own. Swept on the chip (PERF.md section
#: 6, PR 40): the smallest width within 5 % of the best drain. Upstream's own
#: bound is the client's QPS, not a count of threads.
BIND_WINDOW = 32


class LocalTransport:
    """Direct calls into an in-process APIServer (no serialization cost —
    the reference's integration suite does the same with its in-proc
    master). `retry` opts into the pushback budget — the in-proc
    max-inflight filter raises the same 429s the wire path serves."""

    #: a request is a function call that holds the interpreter from end to
    #: end: a second one in flight could only add a hand-over to it
    writes_in_flight = 1

    def __init__(self, api, retry: Optional[RetryPolicy] = None):
        self.api = api
        self.retry = retry

    def request(self, method: str, path: str, query: Dict[str, str],
                body: Optional[Obj]) -> Obj:
        from kubernetes_tpu.apiserver.server import handle_rest

        def once() -> Obj:
            code, obj = handle_rest(self.api, method, path, dict(query), body)
            return obj

        return once() if self.retry is None else self.retry.run(once)

    def stream_watch(self, path: str, query: Dict[str, str]) -> mwatch.Watch:
        from kubernetes_tpu.apiserver.server import handle_rest

        q = dict(query)
        q["watch"] = "true"
        try:
            tag, w = handle_rest(self.api, "GET", path, q, None)
        except errors.StatusError as e:
            # a REFUSED watch (410 Gone on a compacted resume RV, a restart
            # window's 503) surfaces as a terminal watch ERROR event — the
            # same shape the HTTP transport's pump delivers — so the
            # reflector's relist-vs-resume decision reads ONE code path on
            # both transports instead of a raised exception on one and a
            # Status event on the other
            w = mwatch.Watch(capacity=1)
            w.terminate(mwatch.Event(mwatch.ERROR, e.status()))
            return w
        assert tag == "WATCH"
        return w


#: what an `HTTPTransport` counts, by the name a wave's record carries it
#: under (`counters_reader`): requests sent (each attempt), TCP connections
#: dialled for them, re-dials after a kept-alive connection turned out
#: closed, requests that ended in a transport error or a 5xx, body bytes
#: out and in; and from the `http-watch` pump threads the events taken off
#: the streams, the seconds spent decoding and handing them on, and the
#: streams that ended any other way than the server or the consumer
#: ending them
WIRE_COUNTERS = ("http_requests", "http_connections_opened", "http_retries",
                 "http_errors", "http_bytes_out", "http_bytes_in",
                 "watch_events_in", "watch_decode_s", "watch_streams_broken")
#: what a connection the server closed while it sat idle raises on its next
#: use, before a byte of an answer: the request is sent again, once, on a
#: new connection
_STALE = (http.client.RemoteDisconnected, BrokenPipeError,
          ConnectionResetError, ConnectionAbortedError)


class HTTPTransport:
    """The wire path: REST + chunked watch streams. `binary=True` opts the
    client into the negotiated binary codec (machinery/codec.py — the
    `application/vnd.kubernetes.protobuf` seat every internal reference
    client takes, protobuf.go); JSON stays the default and the fallback.

    Connections are kept alive, one per calling thread: a scheduler's
    13,600 Bindings are the `writes_in_flight` connections of its binder's
    threads (sched/server.py `BindWindow`), not 13,600 sockets left in
    TIME_WAIT (a server that answers `Connection: close` is dialled a
    request). A watch stream has a connection of its own for its life.
    Every request and stream is counted (`WIRE_COUNTERS`,
    `counters_reader`), and a request made while a `trace.Trace` is current
    on its thread (a wave's Binding, an informer's first list) files itself
    below the span that caused it as `http.request`, split into `codec`
    (`encode`: the body to bytes; `decode`: the answer's bytes to an object)
    and `wire` (from the first byte sent to the last byte read: the kernel,
    the server's whole handling, the kernel again)."""

    #: events a watch stream's consumer may leave untaken before its pump
    #: waits for it
    watch_buffer = 8192
    #: a request sleeps through its round trip with neither process
    #: computing on it, so a writer of many (a wave's Bindings) may keep
    #: this many in flight, a thread and its connection each
    writes_in_flight = BIND_WINDOW

    def __init__(self, base_url: str, timeout: float = 30.0,
                 token: str = "", binary: bool = False,
                 retry: Optional[RetryPolicy] = None):
        self.base_url = base_url.rstrip("/")
        self.timeout = timeout
        self.token = token
        self.binary = binary
        self.retry = retry
        split = urllib.parse.urlsplit(self.base_url)
        self._dial = http.client.HTTPSConnection \
            if split.scheme == "https" else http.client.HTTPConnection
        self._netloc, self._prefix = split.netloc, split.path
        self._local = threading.local()   # .conn: this thread's connection
        self._mu = threading.Lock()
        self._counts: Dict[str, float] = dict.fromkeys(WIRE_COUNTERS, 0)

    # -- counters ----------------------------------------------------------- #

    def _count(self, **deltas: float) -> None:
        with self._mu:
            for name, n in deltas.items():
                self._counts[name] += n

    def counters(self) -> Dict[str, float]:
        """`WIRE_COUNTERS` since this transport was built."""
        with self._mu:
            return dict(self._counts)

    def counters_reader(self) -> Callable[[], Dict[str, float]]:
        """A reader with a baseline of its own: each call gives what was
        counted since its previous call. A scheduler calls it at each
        wave's end (`Client.store_counters`), so the wave's record says
        what the wire carried while it ran."""
        was = [self.counters()]

        def read() -> Dict[str, float]:
            now = self.counters()
            out = {k: now[k] - was[0][k] for k in WIRE_COUNTERS}
            out["watch_decode_s"] = round(out["watch_decode_s"], 6)
            was[0] = now
            return out

        return read

    # -- requests ----------------------------------------------------------- #

    @staticmethod
    def _with_query(path: str, query: Dict[str, str]) -> str:
        return path + "?" + urllib.parse.urlencode(query) if query else path

    def _url(self, path: str, query: Dict[str, str]) -> str:
        return self.base_url + self._with_query(path, query)

    def _decode_body(self, raw: bytes, content_type: str) -> Obj:
        from kubernetes_tpu.machinery import codec

        if content_type.split(";")[0] == codec.BINARY_MEDIA_TYPE:
            return codec.decode(raw)
        try:
            return json.loads(raw)
        except json.JSONDecodeError:
            return {"raw": raw.decode(errors="replace")}

    def request(self, method: str, path: str, query: Dict[str, str],
                body: Optional[Obj]) -> Obj:
        if self.retry is None:
            return self._request_once(method, path, query, body)
        return self.retry.run(
            lambda: self._request_once(method, path, query, body))

    def _request_once(self, method: str, path: str, query: Dict[str, str],
                      body: Optional[Obj]) -> Obj:
        from kubernetes_tpu.machinery import codec

        pc = time.perf_counter
        t0 = pc()
        # the patch dialect travels as a Content-Type on the wire (the
        # gateway maps it back; apiserver patch.go patchTypes) — pop the
        # local-transport query key and translate
        query = dict(query)
        ptype = query.pop("__patchType", None)
        headers: Dict[str, str] = {}
        data = None
        if self.token:
            headers["Authorization"] = f"Bearer {self.token}"
        if self.binary:
            headers["Accept"] = codec.BINARY_MEDIA_TYPE
        if body is not None:
            if self.binary and method != "PATCH":
                data = codec.encode(body)
                headers["Content-Type"] = codec.BINARY_MEDIA_TYPE
            else:
                # PATCH always rides JSON: the dialect IS the Content-Type,
                # and a binary body would make the server read the dialect
                # as "merge" (patch bodies are partial docs/op lists — the
                # typed binary codec has no frame for them anyway)
                data = json.dumps(body).encode()
                headers["Content-Type"] = {
                    "strategic": "application/strategic-merge-patch+json",
                    "json": "application/json-patch+json",
                    "merge": "application/merge-patch+json",
                }.get(ptype, "application/json")
        t1 = pc()
        code, ctype, raw = self._round_trip(
            method, self._prefix + self._with_query(path, query), data,
            headers)
        t2 = pc()
        try:
            obj = self._decode_body(raw, ctype)
        except Exception as e:  # noqa: BLE001
            if code < 400:
                raise
            raise errors.StatusError(code, "Unknown", str(e))
        tr = trace.current()
        if tr is not None:
            t3 = pc()
            tok = tr.begin("http.request")
            inner = tr.begin("codec")
            tr.child("encode", t1 - t0)
            tr.child("decode", t3 - t2)
            tr.end(inner, t1 - t0 + t3 - t2)
            tr.child("wire", t2 - t1)
            tr.end(tok, t3 - t0)
        if code >= 400:
            raise errors.from_status(obj)
        return obj

    def _round_trip(self, method: str, target: str, data: Optional[bytes],
                    headers: Dict[str, str]) -> Tuple[int, str, bytes]:
        """One request on this thread's connection, dialled if there is
        none: (status code, content type, body). A kept-alive connection
        that the server closed meanwhile is dialled again once."""
        local = self._local
        conn = getattr(local, "conn", None)
        reused = conn is not None
        opened = retried = 0
        status, raw = None, b""
        try:
            while True:
                if conn is None:
                    conn = self._dial(self._netloc, timeout=self.timeout)
                    opened += 1
                try:
                    try:
                        conn.request(method, target, body=data,
                                     headers=headers)
                        r = conn.getresponse()
                    except _STALE:
                        if not reused:
                            raise
                        conn.close()
                        conn, reused, retried = None, False, 1
                        continue
                    raw = r.read()
                except BaseException:
                    conn.close()
                    local.conn = None
                    raise
                if r.will_close:
                    conn.close()
                    conn = None
                local.conn = conn
                status = r.status
                return status, r.getheader("Content-Type") or "", raw
        finally:   # an exception leaves `status` None: a transport error
            self._count(
                http_requests=1 + retried, http_retries=retried,
                http_connections_opened=opened,
                http_errors=1 if status is None or status >= 500 else 0,
                http_bytes_out=(1 + retried) * len(data or b""),
                http_bytes_in=len(raw))

    # -- watch streams ------------------------------------------------------ #

    def stream_watch(self, path: str, query: Dict[str, str]) -> mwatch.Watch:
        q = dict(query)
        q["watch"] = "true"
        q.setdefault("timeoutSeconds", "3600")
        # the socket timeout derives from the timeoutSeconds ACTUALLY sent
        # (plus the request-timeout margin) — a short-timeout watch must
        # hang up when the server does, not 1 h later (the old hardcoded
        # `self.timeout + 3600` kept a 10 s watch's socket open 3610 s)
        try:
            server_timeout = float(q["timeoutSeconds"])
        except (TypeError, ValueError):
            server_timeout = 3600.0
        sock_timeout = self.timeout + server_timeout
        w = mwatch.Watch(capacity=self.watch_buffer)
        pc = time.perf_counter

        def deliver(t0: float, kind: str, obj: Obj) -> bool:
            """Hand one decoded event to the consumer; False once the
            consumer has stopped the watch. A consumer that is behind
            (its buffer full: an informer's handler waiting out a wave
            under the server's lock) holds the PUMP back, and through the
            socket the server, whose own buffer and deaf-watcher contract
            (storage/store.py, 60 s) then decide as they do for a consumer
            in its process: the pump never ends a stream for being late."""
            ev = mwatch.Event(kind, obj)
            sent = w.offer(ev)
            self._count(watch_events_in=1, watch_decode_s=pc() - t0)
            while not sent:
                if w.stopped:
                    return False
                time.sleep(0.002)
                sent = w.offer(ev)
            return True

        def pump_json(r) -> None:
            for raw_line in r:
                if w.stopped:
                    return
                t0 = pc()
                line = raw_line.strip()
                if not line:
                    continue
                ev = json.loads(line)
                if not deliver(t0, ev["type"], ev["object"]):
                    return

        def pump_binary(r) -> None:
            from kubernetes_tpu.machinery import codec

            buf = b""
            while not w.stopped:
                chunk = r.read1(65536)
                if not chunk:
                    return
                t0 = pc()
                buf += chunk
                events, buf = codec.decode_frames(buf)
                for ev in events:
                    if not deliver(t0, ev["type"], ev["object"]):
                        return
                    t0 = pc()

        def pump() -> None:
            from kubernetes_tpu.machinery import codec

            try:
                req = urllib.request.Request(self._url(path, q))
                if self.token:
                    req.add_header("Authorization", f"Bearer {self.token}")
                if self.binary:
                    req.add_header("Accept", codec.BINARY_MEDIA_TYPE)
                self._count(http_requests=1, http_connections_opened=1)
                with urllib.request.urlopen(req, timeout=sock_timeout) as r:
                    ctype = (r.headers.get("Content-Type") or "").split(";")[0]
                    if ctype == codec.BINARY_MEDIA_TYPE:
                        pump_binary(r)
                    else:
                        pump_json(r)
            except urllib.error.HTTPError as e:
                # a refused watch (410 Gone on a compacted resume RV) must
                # surface as a watch ERROR, not masquerade as a clean
                # stream end — the reflector's relist path keys on it
                try:
                    status = self._decode_body(
                        e.read(), e.headers.get("Content-Type", ""))
                except Exception:  # noqa: BLE001
                    status = {"kind": "Status", "code": e.code,
                              "reason": "Unknown"}
                if e.code >= 500:
                    self._count(http_errors=1)
                w.terminate(mwatch.Event(mwatch.ERROR, status))
            except Exception as e:  # noqa: BLE001 - the stream BROKE
                # a connection refused or reset, a timeout, a frame that
                # does not decode: not the server ending the stream. Unless
                # the consumer stopped the watch itself (its own stop closes
                # nothing, but a pump that dies after it is no fault), it is
                # counted and the consumer gets a terminal ERROR, so that a
                # reflector's relist-or-resume decision sees a broken
                # stream as one (a 500: it resumes from its last
                # resourceVersion, under its backoff ladder)
                if not w.stopped:
                    self._count(watch_streams_broken=1, http_errors=1)
                    w.terminate(mwatch.Event(mwatch.ERROR, errors.StatusError(
                        500, "InternalError",
                        f"watch stream broke: {e!r}"[:300]).status()))
            finally:
                w.stop()

        threading.Thread(target=pump, name="http-watch", daemon=True).start()
        return w


class ResourceClient:
    """Verbs for one resource (a typed clientset entry)."""

    def __init__(self, transport, group: str, version: str, resource: str,
                 namespaced: bool):
        self.transport = transport
        self.group = group
        self.version = version
        self.resource = resource
        self.namespaced = namespaced

    def _path(self, namespace: str = "", name: str = "", sub: str = "") -> str:
        root = f"/api/{self.version}" if not self.group else \
            f"/apis/{self.group}/{self.version}"
        parts = [root]
        if self.namespaced and namespace:
            parts.append(f"namespaces/{namespace}")
        parts.append(self.resource)
        if name:
            parts.append(name)
        if sub:
            parts.append(sub)
        return "/".join(parts)

    # -- verbs -------------------------------------------------------------- #

    def create(self, obj: Obj, namespace: str = "") -> Obj:
        ns = namespace or meta.namespace(obj) or ("default" if self.namespaced else "")
        return self.transport.request("POST", self._path(ns), {}, obj)

    def get(self, name: str, namespace: str = "default") -> Obj:
        return self.transport.request("GET", self._path(namespace, name), {}, None)

    def list(self, namespace: str = "", label_selector: str = "",
             field_selector: str = "") -> Obj:
        q = {}
        if label_selector:
            q["labelSelector"] = label_selector
        if field_selector:
            q["fieldSelector"] = field_selector
        return self.transport.request("GET", self._path(namespace), q, None)

    def update(self, obj: Obj, namespace: str = "") -> Obj:
        ns = namespace or meta.namespace(obj)
        return self.transport.request("PUT", self._path(ns, meta.name(obj)),
                                      {}, obj)

    def update_status(self, obj: Obj, namespace: str = "") -> Obj:
        ns = namespace or meta.namespace(obj)
        return self.transport.request(
            "PUT", self._path(ns, meta.name(obj), "status"), {}, obj)

    def patch(self, name: str, patch: Obj, namespace: str = "default",
              patch_type: str = "merge") -> Obj:
        q = {"__patchType": patch_type} if patch_type != "merge" else {}
        return self.transport.request("PATCH", self._path(namespace, name),
                                      q, patch)

    def patch_status(self, name: str, patch: Obj,
                     namespace: str = "default",
                     patch_type: str = "merge") -> Obj:
        q = {"__patchType": patch_type} if patch_type != "merge" else {}
        return self.transport.request(
            "PATCH", self._path(namespace, name, "status"), q, patch)

    def delete(self, name: str, namespace: str = "default",
               resource_version: str = "") -> Obj:
        body = None
        if resource_version:
            body = {"preconditions": {"resourceVersion": resource_version}}
        return self.transport.request("DELETE", self._path(namespace, name),
                                      {}, body)

    def delete_collection(self, namespace: str = "",
                          label_selector: str = "") -> Obj:
        q = {"labelSelector": label_selector} if label_selector else {}
        return self.transport.request("DELETE", self._path(namespace), q, None)

    def watch(self, namespace: str = "", label_selector: str = "",
              field_selector: str = "", resource_version: str = "",
              allow_bookmarks: bool = False,
              timeout_seconds: Optional[int] = None) -> mwatch.Watch:
        q: Dict[str, str] = {}
        if label_selector:
            q["labelSelector"] = label_selector
        if field_selector:
            q["fieldSelector"] = field_selector
        if resource_version:
            q["resourceVersion"] = resource_version
        if allow_bookmarks:
            q["allowWatchBookmarks"] = "true"
        if timeout_seconds is not None:
            # rides to the server AND (HTTP transport) sizes the socket
            # timeout — the two can no longer disagree by an hour
            q["timeoutSeconds"] = str(int(timeout_seconds))
        return self.transport.stream_watch(self._path(namespace), q)

    # -- subresources ------------------------------------------------------- #

    def bind(self, name: str, node_name: str, namespace: str = "default",
             uid: str = "", annotations: Optional[Dict[str, str]] = None
             ) -> Obj:
        binding = {"apiVersion": "v1", "kind": "Binding",
                   "metadata": {"name": name, "namespace": namespace},
                   "target": {"kind": "Node", "name": node_name}}
        if uid:
            binding["metadata"]["uid"] = uid
        if annotations:
            # fencing-token stamping rides here (api.types
            # FENCING_TOKEN_ANNOTATION); the server fences on it
            binding["metadata"]["annotations"] = dict(annotations)
        return self.transport.request(
            "POST", self._path(namespace, name, "binding"), {}, binding)

    def evict(self, name: str, namespace: str = "default") -> Obj:
        return self.transport.request(
            "POST", self._path(namespace, name, "eviction"), {},
            {"apiVersion": "policy/v1beta1", "kind": "Eviction",
             "metadata": {"name": name, "namespace": namespace}})

    def get_scale(self, name: str, namespace: str = "default") -> Obj:
        return self.transport.request("GET", self._path(namespace, name, "scale"),
                                      {}, None)

    def put_scale(self, name: str, replicas: int,
                  namespace: str = "default") -> Obj:
        return self.transport.request(
            "PUT", self._path(namespace, name, "scale"), {},
            {"spec": {"replicas": replicas}})

    def finalize(self, name: str, obj: Obj) -> Obj:
        return self.transport.request("PUT", self._path("", name, "finalize"),
                                      {}, obj)


_KNOWN = {
    # attr: (group, version, resource, namespaced)
    "pods": ("", "v1", "pods", True),
    "nodes": ("", "v1", "nodes", False),
    "namespaces": ("", "v1", "namespaces", False),
    "services": ("", "v1", "services", True),
    "endpoints": ("", "v1", "endpoints", True),
    "events": ("", "v1", "events", True),
    "configmaps": ("", "v1", "configmaps", True),
    "secrets": ("", "v1", "secrets", True),
    "serviceaccounts": ("", "v1", "serviceaccounts", True),
    "persistentvolumes": ("", "v1", "persistentvolumes", False),
    "persistentvolumeclaims": ("", "v1", "persistentvolumeclaims", True),
    "replicationcontrollers": ("", "v1", "replicationcontrollers", True),
    "resourcequotas": ("", "v1", "resourcequotas", True),
    "limitranges": ("", "v1", "limitranges", True),
    "deployments": ("apps", "v1", "deployments", True),
    "replicasets": ("apps", "v1", "replicasets", True),
    "statefulsets": ("apps", "v1", "statefulsets", True),
    "daemonsets": ("apps", "v1", "daemonsets", True),
    "controllerrevisions": ("apps", "v1", "controllerrevisions", True),
    "jobs": ("batch", "v1", "jobs", True),
    "cronjobs": ("batch", "v1beta1", "cronjobs", True),
    "poddisruptionbudgets": ("policy", "v1beta1", "poddisruptionbudgets", True),
    "leases": ("coordination.k8s.io", "v1", "leases", True),
    "endpointslices": ("discovery.k8s.io", "v1beta1", "endpointslices", True),
    "horizontalpodautoscalers": ("autoscaling", "v1",
                                 "horizontalpodautoscalers", True),
    "storageclasses": ("storage.k8s.io", "v1", "storageclasses", False),
    "csinodes": ("storage.k8s.io", "v1", "csinodes", False),
    "priorityclasses": ("scheduling.k8s.io", "v1", "priorityclasses", False),
    "customresourcedefinitions": ("apiextensions.k8s.io", "v1",
                                  "customresourcedefinitions", False),
    "roles": ("rbac.authorization.k8s.io", "v1", "roles", True),
    "rolebindings": ("rbac.authorization.k8s.io", "v1", "rolebindings", True),
    "clusterroles": ("rbac.authorization.k8s.io", "v1", "clusterroles",
                     False),
    "clusterrolebindings": ("rbac.authorization.k8s.io", "v1",
                            "clusterrolebindings", False),
    "certificatesigningrequests": ("certificates.k8s.io", "v1beta1",
                                   "certificatesigningrequests", False),
}


class Client:
    """The clientset: `client.pods.create(...)`, `client.resource(...)`."""

    def __init__(self, transport, store_counters=None):
        self.transport = transport
        # a factory of readers of what lies between this client and the
        # store, for a wave's record: where the store runs in the client's
        # own process (`local`), its watch-plane counters
        # (`Storage.watch_plane_reader`); over the wire, the wire's own
        # (`HTTPTransport.counters_reader`: requests, connections, bytes,
        # the watch pumps); None for a transport that counts nothing
        self.store_counters = store_counters
        self._cache: Dict[Tuple[str, str, str], ResourceClient] = {}

    @staticmethod
    def local(api, retry: Optional[RetryPolicy] = None) -> "Client":
        return Client(LocalTransport(api, retry=retry),
                      store_counters=api.storage.watch_plane_reader)

    @staticmethod
    def http(base_url: str, token: str = "", binary: bool = False,
             retry: Optional[RetryPolicy] = None) -> "Client":
        """`binary=True` negotiates the binary codec for every request and
        watch stream — the internal-client configuration (protobuf.go).
        `retry` opts into the 429/503 pushback budget (RetryPolicy)."""
        transport = HTTPTransport(base_url, token=token, binary=binary,
                                  retry=retry)
        return Client(transport, store_counters=transport.counters_reader)

    def resource(self, group: str, version: str, resource: str,
                 namespaced: bool = True) -> ResourceClient:
        key = (group, version, resource)
        if key not in self._cache:
            self._cache[key] = ResourceClient(self.transport, group, version,
                                              resource, namespaced)
        return self._cache[key]

    def __getattr__(self, attr: str) -> ResourceClient:
        spec = _KNOWN.get(attr)
        if spec is None:
            raise AttributeError(attr)
        return self.resource(spec[0], spec[1], spec[2], spec[3])

    def version(self) -> Obj:
        return self.transport.request("GET", "/version", {}, None)
