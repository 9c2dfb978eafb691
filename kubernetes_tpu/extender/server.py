"""HTTP server exposing the TPU backend at the scheduler-extender boundary.

The stock kube-scheduler's HTTPExtender POSTs JSON to
``{URLPrefix}/{FilterVerb|PrioritizeVerb|PreemptVerb|BindVerb}``
(core/extender.go:424-450 send(): POST, Content-Type application/json, decode
into the result struct). This server speaks exactly that: point a stock
binary's policy at us with::

    {"extenders": [{"urlPrefix": "http://host:port/scheduler",
                    "filterVerb": "filter", "prioritizeVerb": "prioritize",
                    "preemptVerb": "preemption", "bindVerb": "bind",
                    "weight": 1, "nodeCacheCapable": true}]}

and every Filter/Prioritize call is answered from the device lattice.
A /healthz endpoint mirrors the reference's healthz mux (server.go:216-227).

Each request is counted (`extender_requests_total{verb,code}`) and timed from
its arrival to the end of its reply (`extender_request_duration_seconds
{verb}`); the arrival instant and the reply's end also bound the pod's
flight-recorder record (backend.py: `decode` starts at the arrival, `answer`
ends with the reply). `KTPU_TELEMETRY=0` turns both off.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from ..component.metrics import DEFAULT_REGISTRY as REG
from .backend import ExtenderBackend
from .wire import (
    ExtenderArgs,
    ExtenderBindingArgs,
    ExtenderPreemptionArgs,
)

EXTENDER_REQUESTS = REG.counter(
    "extender_requests_total",
    "Scheduler Extender requests answered, by verb and HTTP status",
    labels=("verb", "code"))
EXTENDER_REQUEST_DURATION = REG.histogram(
    "extender_request_duration_seconds",
    "A Scheduler Extender request from its arrival to the end of its reply",
    labels=("verb",))

DEFAULT_VERBS = {
    "filter": "filter",
    "prioritize": "prioritize",
    "preemption": "preemption",
    "bind": "bind",
}


class ExtenderServer:
    """Threaded HTTP server over an ExtenderBackend (test: httptest.NewServer
    analog — extender_test.go:290-312 spins real HTTP servers the same way)."""

    def __init__(
        self,
        backend: ExtenderBackend,
        host: str = "127.0.0.1",
        port: int = 0,
        url_prefix: str = "/scheduler",
        verbs: Optional[dict] = None,
    ) -> None:
        self.backend = backend
        self.url_prefix = url_prefix.rstrip("/")
        self.verbs = dict(DEFAULT_VERBS, **(verbs or {}))
        self.by_path = {path: verb for verb, path in self.verbs.items()}
        self.requests_served = 0

        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # quiet
                pass

            def _reply(self, code: int, obj, encoded=None) -> None:
                # a verb that encodes its own reply hands over the bytes
                body = obj if isinstance(obj, bytes) \
                    else json.dumps(obj).encode()
                if encoded is not None:
                    encoded()   # before a byte leaves: see do_POST
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    self.send_response(200)
                    self.send_header("Content-Length", "2")
                    self.end_headers()
                    self.wfile.write(b"ok")
                else:
                    self._reply(404, {"Error": "not found"})

            def do_POST(self):
                tel = server.backend.telemetry
                t_in = tel.clock()
                length = int(self.headers.get("Content-Length", 0))
                try:
                    payload = json.loads(self.rfile.read(length) or b"{}")
                except json.JSONDecodeError as e:
                    self._reply(400, {"Error": f"bad json: {e}"})
                    return
                path = self.path[len(server.url_prefix):].strip("/")
                verb = server.by_path.get(path)
                server.requests_served += 1
                backend = server.backend
                backend.arrived(t_in)
                try:
                    if verb == "filter":
                        code, obj = 200, backend.filter(
                            ExtenderArgs.from_json(payload)).to_json()
                    elif verb == "prioritize":
                        code, obj = 200, backend.prioritize(
                            ExtenderArgs.from_json(payload)).encode()
                    elif verb == "preemption":
                        code, obj = 200, backend.process_preemption(
                            ExtenderPreemptionArgs.from_json(payload)).to_json()
                    elif verb == "bind":
                        code, obj = 200, backend.bind(
                            ExtenderBindingArgs.from_json(payload)).to_json()
                    else:
                        code, obj = 404, {"Error": f"unknown verb {path!r}"}
                except Exception as e:  # noqa: BLE001 — wire boundary
                    code, obj = 500, {"Error": str(e)}
                if verb is None or not tel.enabled:
                    self._reply(code, obj)
                    return
                # the record's `answer` ends once the reply is encoded and
                # BEFORE a byte of it leaves: the caller's next verb can
                # arrive on another thread as soon as it has read this one
                self._reply(code, obj,
                            encoded=lambda: backend.answered(verb))
                EXTENDER_REQUESTS.inc(verb=verb, code=str(code))
                EXTENDER_REQUEST_DURATION.observe(tel.clock() - t_in,
                                                  verb=verb)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._thread: Optional[threading.Thread] = None

    @property
    def url(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}{self.url_prefix}"

    def start(self) -> "ExtenderServer":
        from ..utils.platform import enable_compile_cache, steady_heap

        enable_compile_cache()  # before the first verb compiles
        steady_heap()  # before the first request is served
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)

    def __enter__(self) -> "ExtenderServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
