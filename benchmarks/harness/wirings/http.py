"""The wiring `http`: the scheduler and the apiserver as two processes with a
socket between them. The apiserver and its native store run in a child
process (`python -m kubernetes_tpu.cli apiserver --port 0`: it imports no
jax, and is started with JAX_PLATFORMS=cpu besides, so it can never take the
chip); everything else is the `local` wiring's, over `Client.http(url)`
(HTTP/1.1, JSON, loopback, connections kept alive): the SchedulerServer, its
APIBinder, the API preemptor and evictor, and a bind-intent ledger whose
records are an API resource written through the client. The benchmark's own
client (set-up, the generator, BindWatch) is a second `Client.http`.

What is the wiring's own: the child's life; the scheduler's transport keeps
the Bindings it was acknowledged; the wire check is handed the URL, those
acknowledgements and the transports' counters (`checks/wire.py`); and the
child's `/metrics` is read when the measured scheduler starts and after the
window (`sources/apiserver_process.py`). README-http.md has the contract.
"""

from __future__ import annotations

import collections
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

from ..checks import wire
from ..sources import apiserver_process
from . import local

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
#: the child has this long to print its URL and answer /healthz
START_LIMIT_S = 15.0


class Cluster(local.Cluster):
    """`local.Cluster` with the apiserver in a process of its own: one child,
    the benchmark's client, and the scheduler servers run against the URL,
    each over a client of its own."""

    def __init__(self, cfg: dict):
        try:   # before anything is started: a tree without them ends at once
            from kubernetes_tpu.client.rest import (  # noqa: F401
                WIRE_COUNTERS, HTTPTransport)
            from kubernetes_tpu.sched.ledger import (  # noqa: F401
                APIBindIntentLedger)
        except ImportError as e:
            raise SystemExit(
                "benchmark: the wiring `http` needs a transport that counts "
                "its requests (client/rest.py WIRE_COUNTERS), a bind-intent "
                "ledger through the client (sched/ledger.py "
                f"APIBindIntentLedger) and `cli apiserver`; this tree: {e}"
            ) from None
        from kubernetes_tpu.client import Client

        class Acknowledged(HTTPTransport):
            """The scheduler's transport; keeps each Binding it was
            acknowledged (the POST returned, so a 2xx), for the wire check."""

            def request(self, method, path, query, body):
                out = super().request(method, path, query, body)
                if method == "POST" and path.endswith("/binding"):
                    acknowledged[body["metadata"]["name"]] = \
                        body["target"]["name"]
                return out

        def client(transport) -> Client:
            self.transports.append(transport)
            return Client(transport, store_counters=transport.counters_reader)

        acknowledged: dict = {}
        self.cfg = cfg
        self.transports: list = []
        self.servers: list = []
        self.child = self.stderr = None
        self.url, self.kvstore = self._spawn()
        self.client = client(HTTPTransport(self.url))
        self.scheduler_client = lambda: client(Acknowledged(self.url))
        self.dims = local.serving_dims(cfg)
        wire.hand(cfg, self.url, acknowledged, self.wire_totals)

    # -- the child ---------------------------------------------------------- #

    def _spawn(self) -> tuple:
        """Start the apiserver's process; (its URL, its store's type). The
        run ends, with the child's stderr, if it exits or stays deaf."""
        self.stderr = tempfile.TemporaryFile(mode="w+")
        self.child = subprocess.Popen(
            [sys.executable, "-m", "kubernetes_tpu.cli", "apiserver",
             "--port", "0", "--exit-with-parent"],
            cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.PIPE, stderr=self.stderr, text=True)
        first: list = []
        reader = threading.Thread(
            target=lambda: first.append(self.child.stdout.readline()),
            daemon=True, name="bench-apiserver-first-line")
        reader.start()
        deadline = time.monotonic() + START_LIMIT_S
        reader.join(START_LIMIT_S)
        try:
            said = json.loads(first[0]) if first and first[0] else None
        except ValueError:
            said = None
        if not isinstance(said, dict) or "url" not in said:
            self._give_up(f"no URL on its first line ({first[:1]!r})")
        while True:
            try:
                with urllib.request.urlopen(said["url"] + "/healthz",
                                            timeout=2) as r:
                    if r.status == 200:
                        break
            except OSError:
                pass
            if self.child.poll() is not None \
                    or time.monotonic() > deadline:
                self._give_up("/healthz did not answer")
            time.sleep(0.05)
        return said["url"], said.get("store", "?")

    def _give_up(self, why: str) -> None:
        rc = self.child.poll()
        self._end_child()
        self.stderr.seek(0)
        tail = self.stderr.read()[-1500:]
        raise SystemExit(
            "benchmark: the wiring `http` could not start the apiserver's "
            f"process (`python -m kubernetes_tpu.cli apiserver`): {why}; "
            f"exit code {rc}; its stderr ends:\n{tail}")

    def _end_child(self) -> None:
        if self.child is None or self.child.poll() is not None:
            return
        self.child.send_signal(signal.SIGTERM)
        try:
            self.child.wait(10)
        except subprocess.TimeoutExpired:
            self.child.kill()
            self.child.wait(10)

    def _scrape(self, which: str) -> None:
        """The child's /metrics, for `sources/apiserver_process.py`."""
        with urllib.request.urlopen(self.url + "/metrics", timeout=30) as r:
            apiserver_process.note(which, time.perf_counter(),
                                   r.read().decode())

    # -- the system under test ---------------------------------------------- #

    def new_server(self):
        from kubernetes_tpu.sched.ledger import APIBindIntentLedger
        from kubernetes_tpu.sched.preemption import APIEvictor, Preemptor
        from kubernetes_tpu.sched.scheduler import Scheduler
        from kubernetes_tpu.sched.server import APIBinder, SchedulerServer

        client = self.scheduler_client()
        sched = Scheduler(binder=APIBinder(client),
                          batch_size=self.dims.P, base_dims=self.dims)
        if self.cfg["preemption"]:
            # victims are evicted through the API, as SchedulerServer wires it
            sched.preemptor = Preemptor(evictor=APIEvictor(client))
        server = SchedulerServer(
            client, scheduler=sched,
            cycle_interval=self.cfg["assumed"]["cycle_interval_s"],
            batch_window=self.cfg["assumed"]["batch_window_s"],
            ledger=APIBindIntentLedger(client, identity="bench")
            if self.cfg["bind_intent_ledger"] else None)
        start = server.start

        def started():
            # the window opens at the measured server's start (the LAST
            # server started): the apiserver's account as it stands then
            self._scrape("open")
            return start()

        server.start = started
        self.servers.append(server)
        return server

    def wire_totals(self) -> dict:
        """The transports' counters, summed over both sides."""
        totals: collections.Counter = collections.Counter()
        for t in self.transports:
            totals.update(t.counters())
        return dict(totals)

    def counters(self, server) -> dict:
        """`local`'s (the ledger is read through the client), the watch
        plane's own guarantees, and the wire's totals for the info line.
        Called right after the window: the apiserver's account is read
        here."""
        self._scrape("close")
        out = super().counters(server)
        totals = self.wire_totals()
        out["zero"]["watch_streams_broken"] = int(
            totals["watch_streams_broken"])
        informers = [i for i in (server.pod_informer, server.node_informer,
                                 server.pdb_informer) if i is not None]
        # a started informer has listed once; any further round is a relist
        out["zero"]["scheduler_informer_relists"] = sum(
            max(i.relists - 1, 0) for i in informers)
        out["info"]["wire_totals"] = {k: round(v, 4)
                                      for k, v in totals.items()}
        out["info"]["apiserver_url"] = self.url
        return out

    def close(self) -> None:
        try:
            for s in self.servers:
                s.stop()
        finally:
            self._end_child()
            if self.stderr is not None:
                self.stderr.close()
