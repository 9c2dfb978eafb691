"""The wire between a scheduler and an apiserver in a process of its own
(ISSUE 39): `HTTPTransport`'s kept-alive connections, counters, spans and
watch pumps; the `apiserver` entry point; the ledger through a client over a
real socket; the wire check; and the two wirings of the benchmark held to one
answer at the rehearsal size.

Every process a test starts is ended in a `finally`, and everything that
waits has a limit of its own: a deaf child fails a test and hangs nothing.
"""

import http.server
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from kubernetes_tpu.apiserver import APIServer, HTTPGateway
from kubernetes_tpu.client import Client
from kubernetes_tpu.client.rest import WIRE_COUNTERS, HTTPTransport
from kubernetes_tpu.component import trace
from kubernetes_tpu.machinery import codec
from kubernetes_tpu.machinery import watch as mwatch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


@pytest.fixture
def gateway():
    api = APIServer()
    gw = HTTPGateway(api).start()
    try:
        yield gw
    finally:
        gw.stop()
        api.close()


def configmap(i: int) -> dict:
    return {"metadata": {"name": f"c{i}"}, "data": {"k": "v"}}


def until(cond, limit: float = 10.0) -> bool:
    deadline = time.monotonic() + limit
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return cond()


# --------------------------------------------------------------------- #
# HTTPTransport: connections
# --------------------------------------------------------------------- #


def test_a_threads_requests_share_its_connection(gateway):
    client = Client.http(gateway.url)
    for i in range(3):
        client.configmaps.create(configmap(i))
    c = client.transport.counters()
    assert c["http_requests"] == 3
    assert c["http_connections_opened"] == 1
    assert c["http_retries"] == c["http_errors"] == 0
    assert c["http_bytes_out"] > 0 and c["http_bytes_in"] > c["http_bytes_out"]


def test_each_thread_has_a_connection_of_its_own(gateway):
    client = Client.http(gateway.url)
    client.configmaps.create(configmap(0))
    threads = [threading.Thread(target=lambda: [
        client.configmaps.get("c0") for _ in range(5)]) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    c = client.transport.counters()
    assert (c["http_requests"], c["http_connections_opened"]) == (16, 4)


class _OneAnswerAConnection(http.server.BaseHTTPRequestHandler):
    """Answers as a server that keeps connections alive (HTTP/1.1, no
    `Connection: close`), then hangs up: the client finds out on its next
    request."""

    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def answer(self, code: int, body: bytes, **headers: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for name, value in headers.items():
            self.send_header(name, value)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        self.answer(200, b'{"ok": true}')
        self.close_connection = True


@pytest.fixture
def scripted():
    """A server whose handler class a test picks; closed in the end."""
    servers = []

    def start(handler):
        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
        srv.daemon_threads = True
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        servers.append(srv)
        return f"http://127.0.0.1:{srv.server_address[1]}"

    try:
        yield start
    finally:
        for srv in servers:
            srv.shutdown()
            srv.server_close()


def test_a_connection_the_server_closed_is_dialled_again_once(scripted):
    t = HTTPTransport(scripted(_OneAnswerAConnection))
    assert t.request("GET", "/x", {}, None) == {"ok": True}
    time.sleep(0.05)   # let the server's close arrive
    assert t.request("GET", "/x", {}, None) == {"ok": True}
    c = t.counters()
    assert c["http_retries"] == 1 and c["http_errors"] == 0
    assert c["http_connections_opened"] == 2
    assert c["http_requests"] == 3   # the second was sent twice


def test_a_server_that_closes_after_each_answer_is_dialled_a_request(scripted):
    class Closes(_OneAnswerAConnection):
        def do_GET(self):
            self.answer(200, b'{"ok": true}', Connection="close")

    t = HTTPTransport(scripted(Closes))
    for _ in range(3):
        assert t.request("GET", "/x", {}, None) == {"ok": True}
    c = t.counters()
    assert (c["http_connections_opened"], c["http_retries"]) == (3, 0)


def test_a_server_that_is_not_there_is_an_error_and_no_retry():
    srv = http.server.HTTPServer(("127.0.0.1", 0), _OneAnswerAConnection)
    port = srv.server_address[1]
    srv.server_close()
    t = HTTPTransport(f"http://127.0.0.1:{port}", timeout=2)
    with pytest.raises(OSError):
        t.request("GET", "/x", {}, None)
    c = t.counters()
    assert (c["http_errors"], c["http_retries"]) == (1, 0)


def test_a_5xx_is_counted_and_a_4xx_is_not(gateway, scripted):
    class Broken(_OneAnswerAConnection):
        def do_GET(self):
            self.answer(503, json.dumps({
                "kind": "Status", "code": 503, "reason": "ServiceUnavailable",
                "message": "down"}).encode())

    from kubernetes_tpu.machinery import errors

    t = HTTPTransport(scripted(Broken))
    with pytest.raises(errors.StatusError) as e:
        t.request("GET", "/x", {}, None)
    assert e.value.code == 503 and t.counters()["http_errors"] == 1
    client = Client.http(gateway.url)
    with pytest.raises(errors.StatusError) as e:
        client.configmaps.get("nope")
    assert e.value.code == 404
    assert client.transport.counters()["http_errors"] == 0


# --------------------------------------------------------------------- #
# HTTPTransport: spans and the record's counters
# --------------------------------------------------------------------- #


def test_a_request_files_itself_below_the_span_that_caused_it(gateway):
    client = Client.http(gateway.url)
    client.configmaps.create(configmap(0))   # no Trace current: nothing
    tr = trace.Trace("wave", clock=time.perf_counter)
    token = trace.activate(tr)
    try:
        tok = tr.begin("bind-call")
        t0 = time.perf_counter()
        client.configmaps.create(configmap(1))
        client.configmaps.get("c1")
        tr.end(tok, time.perf_counter() - t0)
        tr.step("bind-commit")
    finally:
        trace.deactivate(token)
    ch = tr.children()
    base = "bind-commit/bind-call/http.request"
    assert {p for p in ch if p.startswith(base)} == {
        base, base + "/codec", base + "/codec/encode",
        base + "/codec/decode", base + "/wire"}
    assert all(ch[p][0] == 2 for p in ch if p.startswith(base))
    whole, wire = ch[base][1], ch[base + "/wire"][1]
    parts = wire + ch[base + "/codec"][1]
    assert 0 < wire <= parts <= whole <= ch["bind-commit/bind-call"][1]
    assert ch[base + "/codec"][1] == pytest.approx(
        ch[base + "/codec/encode"][1] + ch[base + "/codec/decode"][1])


def test_the_clients_reader_gives_what_the_wire_carried_since_its_last_call(
        gateway):
    client = Client.http(gateway.url)
    read = client.store_counters()
    assert set(read()) == set(WIRE_COUNTERS)
    client.configmaps.create(configmap(0))
    client.configmaps.get("c0")
    first, second = read(), read()
    assert first["http_requests"] == 2 and first["http_bytes_out"] > 0
    assert not any(second.values())
    # the in-process client's factory reads the STORE's watch plane
    assert "pump_lag_max" in Client.local(gateway.api).store_counters()()


# --------------------------------------------------------------------- #
# HTTPTransport: watch streams
# --------------------------------------------------------------------- #


def test_a_pump_counts_its_events_and_a_clean_end_is_no_break(gateway):
    client = Client.http(gateway.url)
    rv = client.configmaps.list("default")["metadata"]["resourceVersion"]
    w = client.configmaps.watch("default", resource_version=rv,
                                timeout_seconds=1)
    for i in range(5):
        client.configmaps.create(configmap(i))
    names = []
    while len(names) < 5:
        ev = w.next(timeout=5)
        assert ev is not None and ev.type == mwatch.ADDED
        names.append(ev.object["metadata"]["name"])
    assert names == [f"c{i}" for i in range(5)]
    assert until(lambda: w.stopped, 5)   # the server's timeoutSeconds
    assert w.next(timeout=0.1) is None   # ended, and no ERROR
    c = client.transport.counters()
    assert c["watch_events_in"] == 5 and c["watch_decode_s"] > 0
    assert c["watch_streams_broken"] == 0


def test_a_consumer_that_is_behind_holds_the_pump_and_loses_nothing(gateway):
    client = Client.http(gateway.url)
    client.transport.watch_buffer = 4
    rv = client.configmaps.list("default")["metadata"]["resourceVersion"]
    w = client.configmaps.watch("default", resource_version=rv)
    try:
        for i in range(40):
            client.configmaps.create(configmap(i))
        time.sleep(0.5)   # the buffer is full and the pump waits
        assert not w.stopped and w.depth() == 4
        got = [w.next(timeout=5).object["metadata"]["name"]
               for _ in range(40)]
        assert got == [f"c{i}" for i in range(40)]
        assert client.transport.counters()["watch_streams_broken"] == 0
    finally:
        w.stop()


class _Refuses(_OneAnswerAConnection):
    def do_GET(self):
        self.answer(410, json.dumps({"kind": "Status", "code": 410,
                                     "reason": "Gone",
                                     "message": "too old"}).encode())


def test_a_refused_watch_is_an_error_event_and_no_broken_stream(scripted):
    t = HTTPTransport(scripted(_Refuses))
    w = t.stream_watch("/api/v1/pods", {"resourceVersion": "1"})
    ev = w.next(timeout=5)
    assert ev is not None and ev.type == mwatch.ERROR
    assert ev.object["code"] == 410
    assert until(lambda: w.stopped, 5)
    assert t.counters()["watch_streams_broken"] == 0


def _cut_off(content_type: str, head: bytes):
    class CutOff(_OneAnswerAConnection):
        """One whole event, the start of another, and the socket is gone
        with the chunked body unfinished."""

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Type", content_type)
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            self.wfile.write(f"{len(head):x}\r\n".encode() + head + b"\r\n")
            self.wfile.write(b"40\r\n{\"type\": \"ADD")
            self.wfile.flush()
            self.connection.shutdown(2)

    return CutOff


EVENT = {"type": "ADDED", "object": {"metadata": {"name": "a"}}}


@pytest.mark.parametrize("content_type, head", [
    ("application/json", json.dumps(EVENT).encode() + b"\n"),
    (codec.BINARY_MEDIA_TYPE, codec.encode_frame(EVENT)),
], ids=["json", "binary"])
def test_a_pump_that_dies_is_counted_and_ends_the_watch_with_an_error(
        scripted, content_type, head):
    t = HTTPTransport(scripted(_cut_off(content_type, head)),
                      binary=content_type != "application/json")
    w = t.stream_watch("/api/v1/pods", {})
    first = w.next(timeout=5)
    assert first is not None and first.type == "ADDED"
    last = w.next(timeout=5)
    assert last is not None and last.type == mwatch.ERROR
    assert last.object["code"] == 500
    assert "watch stream broke" in last.object["message"]
    assert until(lambda: w.stopped, 5)
    c = t.counters()
    assert c["watch_streams_broken"] == 1 and c["watch_events_in"] == 1


def test_a_watch_its_consumer_stopped_is_no_broken_stream(gateway):
    client = Client.http(gateway.url)
    w = client.configmaps.watch("default")
    time.sleep(0.2)
    w.stop()
    client.configmaps.create(configmap(0))   # wakes the pump: it leaves
    time.sleep(0.3)
    assert client.transport.counters()["watch_streams_broken"] == 0


def test_an_informer_resumes_over_a_broken_stream_without_a_relist(scripted,
                                                                   gateway):
    """The reflector's decision: a broken stream is an ERROR that is not a
    410, so it re-watches from its last resourceVersion."""
    from kubernetes_tpu.client.informers import SharedInformer

    client = Client.http(gateway.url)
    real = client.transport.stream_watch
    cut = {"left": 1}

    def stream_watch(path, query):
        if cut["left"]:
            cut["left"] -= 1
            w = mwatch.Watch(capacity=4)
            w.terminate(mwatch.Event(mwatch.ERROR, {
                "kind": "Status", "code": 500, "reason": "InternalError",
                "message": "watch stream broke: test"}))
            return w
        return real(path, query)

    client.transport.stream_watch = stream_watch
    inf = SharedInformer(client.configmaps, namespace="default")
    seen = []
    inf.add_handlers(on_add=lambda o: seen.append(o["metadata"]["name"]))
    inf.start()
    try:
        assert inf.wait_for_sync(10)
        client.configmaps.create(configmap(7))
        assert until(lambda: "c7" in seen, 10)
        assert inf.relists == 1
    finally:
        inf.stop()


# --------------------------------------------------------------------- #
# the ledger over a real socket (the drills are tests/test_restart.py's
# `api` and `api-http` cases)
# --------------------------------------------------------------------- #


def test_the_ledger_through_a_client_keeps_the_storage_forms_records(
        gateway):
    from kubernetes_tpu.sched.ledger import (APIBindIntentLedger,
                                             BindIntentLedger)

    over_wire = APIBindIntentLedger(Client.http(gateway.url), identity="a")
    in_store = BindIntentLedger(gateway.api.storage, identity="b")
    one = over_wire.write_intent(3, 1, {"default/p0": "n0"})
    two = in_store.write_intent(4, 1, {"default/p1": "n1"})
    for ledger in (over_wire, in_store):
        left = ledger.unretired()
        assert [(i.key, i.cycle, i.holder, i.bindings) for i in left] == [
            (one.key, 3, "a", {"default/p0": "n0"}),
            (two.key, 4, "b", {"default/p1": "n1"})]
    assert over_wire.retire(two) and in_store.retire(one)
    assert not over_wire.retire(one)   # gone already: no error, not counted
    assert over_wire.unretired() == [] == in_store.unretired()
    assert (over_wire.intents_written, over_wire.intents_retired) == (1, 1)
    with pytest.raises(ValueError):
        APIBindIntentLedger(Client.http(gateway.url), "tenant/sched")


def test_another_schedulers_intents_are_not_this_ones(gateway):
    from kubernetes_tpu.sched.ledger import APIBindIntentLedger

    client = Client.http(gateway.url)
    mine = APIBindIntentLedger(client, "default-scheduler")
    other = APIBindIntentLedger(client, "batch-scheduler")
    other.write_intent(1, 0, {"default/p": "n"})
    assert mine.unretired() == [] and len(other.unretired()) == 1


# --------------------------------------------------------------------- #
# a wave's Bindings, a window of them in flight (ISSUE 40)
# --------------------------------------------------------------------- #


class _Logged(HTTPTransport):
    """A scheduler's transport as the benchmark's wiring subclasses it:
    `request()` overridden, here to keep (method, path, the instant the
    answer was in hand) of every request that passed through it."""

    def __init__(self, url: str):
        super().__init__(url)
        self.log: list = []

    def request(self, method, path, query, body):
        try:
            return super().request(method, path, query, body)
        finally:
            self.log.append((method, path, time.perf_counter()))

    def bindings(self) -> list:
        return [(path.split("/")[-2], at) for method, path, at in self.log
                if method == "POST" and path.endswith("/binding")]


def _populate(client, pods: int, nodes: int = 8) -> None:
    for i in range(nodes):
        client.nodes.create({
            "metadata": {"name": f"n{i}"},
            "status": {"allocatable": {"cpu": "64", "memory": "256Gi",
                                       "pods": "110"}}})
    for i in range(pods):
        client.pods.create({
            "metadata": {"name": f"p{i}", "namespace": "default"},
            "spec": {"containers": [{
                "name": "c", "image": "i",
                "resources": {"requests": {"cpu": "100m"}}}]}})


def _scheduler_over(client):
    """A Scheduler that knows what `client` lists, its binder and its
    bind-intent ledger through `client` too, as the `http` wiring builds
    them; the pods queued in the order of their names' numbers."""
    from kubernetes_tpu.api.v1 import node_from_v1, pod_from_v1
    from kubernetes_tpu.sched.ledger import APIBindIntentLedger
    from kubernetes_tpu.sched.scheduler import Scheduler
    from kubernetes_tpu.sched.server import APIBinder

    sched = Scheduler(binder=APIBinder(client), batch_size=512)
    sched.prewarmer.enabled = False
    sched.ledger = APIBindIntentLedger(client, identity="t")
    for obj in client.nodes.list()["items"]:
        sched.on_node_add(node_from_v1(obj))
    for obj in sorted(client.pods.list("default")["items"],
                      key=lambda o: int(o["metadata"]["name"][1:])):
        sched.on_pod_add(pod_from_v1(obj))
    return sched


def _bound(client) -> dict:
    return {p["metadata"]["name"]: p["spec"]["nodeName"]
            for p in client.pods.list("default")["items"]
            if p["spec"].get("nodeName")}


@pytest.fixture(scope="module")
def windowed_wave():
    """One traced wave of 300 pods over a served apiserver, through a
    transport whose `request()` a subclass overrides; what it left."""
    from kubernetes_tpu.client.rest import BIND_WINDOW

    api = APIServer()
    gw = HTTPGateway(api).start()
    try:
        transport = _Logged(gw.url)
        client = Client(transport, store_counters=transport.counters_reader)
        _populate(Client.http(gw.url), pods=300)
        sched = _scheduler_over(client)
        assert sched.binder.window().width == BIND_WINDOW > 1
        before = transport.counters()
        threads = {t.name for t in threading.enumerate()}
        stats = sched.schedule_pending()
        yield {
            "stats": stats, "log": list(transport.log),
            "bindings": transport.bindings(),
            "opened": transport.counters()["http_connections_opened"]
            - before["http_connections_opened"],
            "threads": {t.name for t in threading.enumerate()
                        if not t.name.endswith("(process_request_thread)")}
            - threads,   # (the served apiserver's own: a connection each)
            "unretired": sched.ledger.unretired(),
            "record": sched.telemetry.recorder.snapshot("test")[
                "records"][-1],
            "bound": _bound(Client.http(gw.url)), "width": BIND_WINDOW}
        sched.binder.close()
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("bind-window")]
    finally:
        gw.stop()
        api.close()


def test_a_wave_over_the_wire_binds_each_pod_exactly_once(windowed_wave):
    w = windowed_wave
    assert w["stats"].scheduled == 300 and w["stats"].bind_errors == 0
    # one POST a pod, each through the subclass's `request()`, and what
    # the apiserver lists is what the wave decided
    names = [name for name, _at in w["bindings"]]
    assert sorted(names) == sorted(f"p{i}" for i in range(300))
    assert w["bound"] == {k.split("/")[1]: v
                          for k, v in w["stats"].assignments.items()}


def test_the_intent_is_retired_after_the_last_answer(windowed_wave):
    w = windowed_wave
    intents = [(m, at) for m, path, at in w["log"] if "bindintents" in path]
    assert [m for m, _ in intents] == ["POST", "DELETE"]
    answers = [at for _name, at in w["bindings"]]
    # written before the first Binding went out, retired after the last
    # was answered, and nothing of it left
    assert intents[0][1] < min(answers) and max(answers) < intents[1][1]
    assert w["unretired"] == []


def test_the_window_is_its_threads_connections_and_no_more(windowed_wave):
    w = windowed_wave
    assert w["threads"] == {f"bind-window-{i}" for i in range(w["width"])}
    # a connection a binder thread, and the wave's own thread's (the
    # intent's write and retire)
    assert w["opened"] <= w["width"] + 1


def test_a_traced_wave_over_the_wire_keeps_the_requests_spans(
        windowed_wave):
    rec = windowed_wave["record"]
    ch = rec["children"]
    base = "bind-commit/bind-call"
    for path in (base, base + "/http.request", base + "/http.request/wire",
                 base + "/http.request/codec", "bind-commit/assume",
                 "bind-commit/finish"):
        assert ch[path][0] == 300, path
    # `bind-call` is the committing thread's wall seconds, inside its
    # phase; the requests' own seconds overlap and pass it
    phase = dict(rec["phases"])["bind-commit"]
    assert ch[base][1] <= phase
    assert ch[base + "/http.request/wire"][1] \
        <= ch[base + "/http.request"][1]
    # the two numbers `bind_in_flight_mean` is the ratio of: the requests'
    # seconds are the workers' own, a little over the spans below them
    assert rec["bind_window_s"] <= phase
    assert rec["bind_request_s"] >= ch[base + "/http.request"][1]
    assert 1.0 < rec["bind_request_s"] / rec["bind_window_s"] \
        <= windowed_wave["width"]


def test_a_binding_refused_mid_wave_rolls_back_that_pod_alone(gateway):
    other = Client.http(gateway.url)
    _populate(other, pods=40)
    transport = _Logged(gateway.url)
    sched = _scheduler_over(Client(transport))
    try:
        # after the scheduler listed them: p3 is bound by someone else
        # (409 already assigned), p5 is deleted (404)
        other.pods.bind("p3", "n7")
        other.pods.delete("p5")
        stats = sched.schedule_pending()
    finally:
        sched.binder.close()
    assert stats.scheduled == 38 and stats.bind_errors == 2
    assert sorted(stats.failed_keys) == ["default/p3", "default/p5"]
    assert len(transport.bindings()) == 40   # one write each, none again
    for key in stats.failed_keys:   # forgotten, and back in the queue
        assert sched.cache.get_pod(key) is None
        assert sched.queue.get_pod(key) is not None
    bound = _bound(other)
    assert bound.pop("p3") == "n7"
    assert bound == {k.split("/")[1]: v
                     for k, v in stats.assignments.items()}
    assert sched.ledger.unretired() == []


def test_through_the_local_transport_no_thread_and_the_waves_order():
    """`LocalTransport` says one write at a time: the binder has no window,
    the commit starts no thread and the Bindings reach the apiserver in
    the order the wave settled them."""
    from kubernetes_tpu.client.rest import LocalTransport

    class Ordered(LocalTransport):
        order: list = []

        def request(self, method, path, query, body):
            if method == "POST" and path.endswith("/binding"):
                self.order.append((path.split("/")[-2],
                                   threading.current_thread().name))
            return super().request(method, path, query, body)

    api = APIServer()
    try:
        _populate(Client.local(api), pods=60)
        sched = _scheduler_over(Client(Ordered(api)))
        assert sched.binder.window() is None
        threads = set(threading.enumerate())
        stats = sched.schedule_pending()
        # (the dispatch has a watchdog's worker; the commit has no one)
        assert not [t.name for t in set(threading.enumerate()) - threads
                    if t.name.startswith("bind-window")]
        assert stats.scheduled == 60 and not stats.bind_request_s
        assert [name for name, _ in Ordered.order] == [
            k.split("/")[1] for k in stats.assignments]
        assert {thread for _, thread in Ordered.order} == {
            threading.current_thread().name}
        by_revision = sorted(
            Client.local(api).pods.list("default")["items"],
            key=lambda p: int(p["metadata"]["resourceVersion"]))
        assert [p["metadata"]["name"] for p in by_revision] == [
            name for name, _ in Ordered.order]
    finally:
        api.close()


# --------------------------------------------------------------------- #
# the apiserver alone, as a process
# --------------------------------------------------------------------- #


def start_apiserver(*args):
    return subprocess.Popen(
        [sys.executable, "-m", "kubernetes_tpu.cli", "apiserver",
         "--port", "0", *args],
        cwd=ROOT, env=ENV, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


def first_line(child, limit: float = 30.0) -> dict:
    got = []
    t = threading.Thread(target=lambda: got.append(child.stdout.readline()),
                         daemon=True)
    t.start()
    t.join(limit)
    assert got and got[0], f"no first line in {limit} s"
    return json.loads(got[0])


def end(child) -> int:
    if child.poll() is None:
        child.send_signal(signal.SIGTERM)
        try:
            return child.wait(15)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait(15)
            raise
    return child.returncode


def test_the_apiserver_entry_point_serves_alone_and_ends_on_sigterm():
    child = start_apiserver()
    try:
        said = first_line(child)
        assert said["store"] in ("NativeKV", "PyKV")
        assert said["pid"] == child.pid
        url = said["url"]
        with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
            assert r.read() == b"ok"
        client = Client.http(url)
        client.configmaps.create(configmap(0))
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "apiserver_request_duration_seconds_sum" in text
        assert "storage_txn_duration_seconds" in text
        cpu = [ln for ln in text.splitlines()
               if ln.startswith("process_cpu_seconds_total ")]
        assert cpu and float(cpu[0].split()[1]) > 0
        # jax is not in its process: none of jaxlib is mapped into it
        maps = f"/proc/{child.pid}/maps"
        if os.path.exists(maps):
            with open(maps) as f:
                assert not [ln for ln in f if "jaxlib" in ln
                            or "libtpu" in ln]
        assert end(child) == 0
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(15)


def test_the_apiserver_subcommand_imports_no_jax():
    done = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys\n"
         "sys.argv = ['kubernetes_tpu.cli', 'apiserver', '--help']\n"
         "try:\n"
         "    runpy.run_module('kubernetes_tpu.cli', run_name='__main__')\n"
         "except SystemExit:\n"
         "    pass\n"
         "import kubernetes_tpu.cli.apiserver, kubernetes_tpu.apiserver\n"
         "print('jax' in sys.modules, 'jaxlib' in sys.modules)\n"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=60)
    assert done.stdout.strip().splitlines()[-1] == "False False", done.stderr


def test_the_cli_packages_names_still_resolve():
    from kubernetes_tpu import cli

    assert cli.Cluster.__name__ == "Cluster" and callable(cli.main)
    with pytest.raises(AttributeError):
        cli.nothing_of_the_kind


def test_an_apiserver_whose_parent_is_gone_ends_itself():
    """`--exit-with-parent`: the benchmark's wiring starts its child so, and
    a harness killed outright leaves no apiserver behind."""
    parent = subprocess.Popen(
        [sys.executable, "-c",
         "import subprocess, sys, time\n"
         "c = subprocess.Popen([sys.executable, '-m', 'kubernetes_tpu.cli', "
         "'apiserver', '--port', '0', '--exit-with-parent'], "
         "stdout=subprocess.PIPE, text=True)\n"
         "print(c.stdout.readline().strip(), flush=True)\n"
         "time.sleep(60)\n"],
        cwd=ROOT, env=ENV, stdout=subprocess.PIPE, text=True)
    pid = None
    try:
        pid = first_line(parent)["pid"]
        parent.kill()
        parent.wait(15)

        def gone() -> bool:
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                return True
            # a zombie nobody reaps still answers kill(0)
            try:
                with open(f"/proc/{pid}/stat") as f:
                    return f.read().rsplit(")", 1)[1].split()[0] == "Z"
            except OSError:
                return True

        assert until(gone, 15)
    finally:
        if parent.poll() is None:
            parent.kill()
            parent.wait(15)
        if pid is not None:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass


# --------------------------------------------------------------------- #
# the wire check (benchmarks/harness/checks/wire.py), without a run
# --------------------------------------------------------------------- #


def test_the_wire_check_reads_nothing_where_nothing_was_handed():
    from benchmarks.harness.checks import wire

    ctx = {"cfg": {"wiring": "local"}}
    assert wire.final_state([], [], ctx) == []
    assert wire.replay([], [], [("bound", "p", "n")], {"p": {}}, [],
                       ctx) == (0, [])


@pytest.mark.parametrize("acknowledged, seen, bad", [
    ({"p0": "n0", "p1": "n1"}, {"p0": "n0", "p1": "n1"}, 0),
    ({"p0": "n0", "p1": "n9"}, {"p0": "n0", "p1": "n1"}, 2),   # elsewhere
    ({"p0": "n0", "ghost": "n1"}, {"p0": "n0"}, 0),   # not a pod of the run
    ({"p0": "n0", "p2": "n1"}, {"p0": "n0"}, 2),   # never stored, never seen
], ids=["all-there", "on-another-node", "not-the-runs", "never-stored"])
def test_the_wire_check_looks_each_acknowledgement_up_over_a_new_connection(
        gateway, acknowledged, seen, bad):
    from benchmarks.harness.checks import wire

    client = Client.local(gateway.api)
    client.nodes.create({"metadata": {"name": "n0"}})
    for name, node in (("p0", "n0"), ("p1", "n1"), ("p2", "")):
        client.pods.create({"metadata": {"name": name}, "spec": {
            "containers": [{"name": "c", "image": "i"}]}})
        if node:
            client.pods.bind(name, node)
    cfg = {"wiring": "http"}
    wire.hand(cfg, gateway.url, acknowledged,
              lambda: {"http_errors": 2, "http_retries": 1})
    try:
        ctx = {"cfg": cfg}
        assert len(wire.final_state([], [], ctx)) == 3
        history = [("bound", n, node) for n, node in seen.items()]
        looked, found = wire.replay(
            [], [], history, {"p0": {}, "p1": {}, "p2": {}}, [], ctx)
        assert looked == len([n for n in acknowledged if n != "ghost"])
        assert len(found) == bad, found
        # another run's hand-over is not this run's
        assert wire.replay([], [], history, {"p0": {}}, [],
                           {"cfg": dict(cfg)}) == (0, [])
    finally:
        wire.HANDED.clear()


def test_the_apiserver_process_reader_takes_growth_between_two_readings():
    from benchmarks.harness.sources import apiserver_process as src

    def text(total, cpu):
        return ("# TYPE apiserver_request_duration_seconds histogram\n"
                'apiserver_request_duration_seconds_sum{verb="create",'
                'resource="pods",subresource=""} 9.0\n'
                'apiserver_request_duration_seconds_sum{verb="create",'
                f'resource="pods",subresource="binding"}} {total}\n'
                f"process_cpu_seconds_total {cpu}\n")

    bind = {"select": "sum", "metric": "apiserver_request_duration_seconds",
            "labels": {"verb": "create", "resource": "pods",
                       "subresource": "binding"}}
    cpu = {"select": "rate", "metric": "process_cpu_seconds_total"}
    try:
        src.READINGS.clear()
        assert src.read({}, bind) is None      # nothing was handed over
        src.note("close", 5.0, text(1.0, 2.0))  # the reading before a start
        src.note("open", 10.0, text(1.5, 3.0))
        assert src.read({}, cpu) is None       # no reading after the start
        src.note("close", 20.0, text(4.0, 9.0))
        assert src.read({}, bind) == pytest.approx(2.5)
        assert src.read({}, cpu) == pytest.approx(0.6)
        assert src.read({}, {"select": "rate", "metric": "absent"}) is None
    finally:
        src.READINGS.clear()


def test_the_field_ratio_reader_sums_before_it_divides():
    from benchmarks.harness.sources import field_ratio

    spec = {"over": "watch_decode_s", "under": "watch_events_in"}
    waves = [{"watch_decode_s": 0.5, "watch_events_in": 100},
             {"watch_decode_s": 0.1, "watch_events_in": 900},
             {"attempted": 3}]
    assert field_ratio.read({"waves": waves}, spec) == pytest.approx(0.0006)
    assert field_ratio.read({"waves": waves[2:]}, spec) is None


# --------------------------------------------------------------------- #
# the two wirings, one answer (whole rehearsals, each a process with a
# limit of its own)
# --------------------------------------------------------------------- #

REHEARSAL_LIMIT_S = 900


def rehearse(command: list) -> tuple:
    """(the info line, the result line(s), stderr) of one rehearsal
    process."""
    done = subprocess.run([sys.executable, *command], cwd=ROOT, env=ENV,
                          capture_output=True, text=True,
                          timeout=REHEARSAL_LIMIT_S)
    lines = done.stdout.splitlines()
    info = [json.loads(ln[5:]) for ln in lines if ln.startswith("info ")]
    results = [json.loads(ln) for ln in lines if ln.startswith("{")]
    assert info and results, done.stderr[-2000:]
    return info[-1], results, done.stderr


def checks_of(result: dict) -> dict:
    return {name: c["value"] for name, c in result["checks"].items()}


@pytest.fixture(scope="module")
def both_wirings():
    """The SAME seed through `flagship-5k.backlog` (wiring `local`) and
    `flagship-5k-http.backlog` (wiring `http`), side by side."""
    seed, out = "20390039", {}

    def run(cell):
        out[cell] = rehearse(["benchmarks/run.py", "--workload", cell,
                              "--seed", seed, "--seconds", "40", "--trace",
                              "1", "--rehearse"])

    threads = [threading.Thread(target=run, args=(c,)) for c in
               ("flagship-5k.backlog", "flagship-5k-http.backlog")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(REHEARSAL_LIMIT_S + 30)
    assert len(out) == 2
    return out["flagship-5k.backlog"], out["flagship-5k-http.backlog"]


def test_the_two_wirings_place_every_pod_alike(both_wirings):
    (local_info, local_res, _), (http_info, http_res, _) = both_wirings
    assert local_res[-1]["correct"] and http_res[-1]["correct"]
    assert local_info["placements_sha256"] == http_info["placements_sha256"]
    assert local_res[-1]["attempted"] == http_res[-1]["attempted"] == 800
    assert local_res[-1]["failed"] == http_res[-1]["failed"] == 0


def test_the_http_wirings_run_is_held_to_the_wire_and_reads_zero(
        both_wirings):
    _local, (info, results, _) = both_wirings
    checks = checks_of(results[-1])
    for name in ("wire_request_errors", "bindings_acknowledged_not_listed",
                 "watch_streams_broken", "scheduler_informer_relists",
                 "intents_unretired", "invariant_violations",
                 "bindings_infeasible_at_their_turn", "pods_never_bound"):
        assert checks[name] == 0, name
    totals = info["wire_totals"]
    assert totals["http_errors"] == totals["http_retries"] == 0
    # connections are kept alive: a handful for two thousand requests, and
    # one a binder thread of each of the run's two schedulers (ISSUE 40)
    from kubernetes_tpu.client.rest import BIND_WINDOW

    assert totals["http_requests"] > 1600
    assert totals["http_connections_opened"] < 40 + 2 * BIND_WINDOW


def test_the_http_cells_traced_line_carries_every_metric_listed_for_it(
        both_wirings):
    from benchmarks.harness import cell

    _local, (_info, results, _) = both_wirings
    bench = cell.load_json(cell.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in cell.metrics_of(
        bench, "per_layer", "flagship-5k-http.backlog")}
    got = results[-1]["metrics"]
    assert set(got) == listed
    assert all(isinstance(m["value"], float) for m in got.values())
    assert got["http_connections_per_bind"]["value"] < 0.05
    assert 0 < got["apiserver_process_cpu_share"]["value"] < 4
    assert got["bind_http_wire_ms_per_pod"]["value"] \
        > got["apiserver_process_bind_ms_per_pod"]["value"] > 0
    # none that reads the store's own process
    assert not {"apiserver_bind_ms_per_pod", "store_txn_ms_per_pod",
                "pump_lag_max_events", "pump_busy_ms_per_pod",
                "watch_evictions", "start_pods_list_kv_s"} & set(got)


@pytest.mark.parametrize("control, failed", [
    ("drop_answer_after_store", {"wire_request_errors"}),
    ("forge_acknowledgement", {"bindings_acknowledged_not_listed",
                               "pods_never_bound"}),
])
def test_a_forged_wire_is_not_correct(control, failed):
    _info, results, _ = rehearse([
        "benchmarks/tests/chip_control_http.py", "--workload",
        "flagship-5k-http.backlog", "--control", control, "--seeds",
        "20390040", "--seconds", "15", "--rehearse"])
    run, summary = results[0], results[-1]
    assert summary == {"workload": "flagship-5k-http.backlog",
                       "control": control, "runs": 1, "not_correct": 1}
    assert run["correct"] is False
    nonzero = {name for name, v in checks_of(run).items() if v}
    assert failed <= nonzero, nonzero
