"""Client machinery: clientset verbs, informers, workqueue, leader election.

Mirrors client-go's tools/cache + util/workqueue + tools/leaderelection test
coverage, run against a real in-process apiserver (both transports).
"""

import threading
import time

import pytest

from kubernetes_tpu.apiserver import APIServer, HTTPGateway
from kubernetes_tpu.client import (
    Client,
    EventBroadcaster,
    EventRecorder,
    InformerFactory,
    LeaderElectionConfig,
    LeaderElector,
    RateLimitingQueue,
    SharedInformer,
    WorkQueue,
    pods_by_node_index,
)
from kubernetes_tpu.machinery import errors


@pytest.fixture
def api():
    a = APIServer()
    yield a
    a.close()


@pytest.fixture(params=["local", "http"])
def client(request, api):
    if request.param == "local":
        yield Client.local(api)
    else:
        gw = HTTPGateway(api).start()
        yield Client.http(gw.url)
        gw.stop()


def wait_for_watch(inf, timeout=5.0):
    """Poll until the informer's live watch exists (it is established after
    _synced is set, so wait_for_sync alone does not guarantee it)."""
    deadline = time.monotonic() + timeout
    while inf._watch is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert inf._watch is not None, "informer watch not established in time"
    return inf._watch


def mkpod(name, ns="default", node="", labels=None):
    p = {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": name, "namespace": ns},
         "spec": {"containers": [{"name": "c", "image": "img"}]}}
    if labels:
        p["metadata"]["labels"] = labels
    if node:
        p["spec"]["nodeName"] = node
    return p


class TestClientVerbs:
    def test_crud_and_bind(self, client):
        client.pods.create(mkpod("a"))
        got = client.pods.get("a")
        assert got["metadata"]["name"] == "a"
        client.pods.bind("a", "n1", uid=got["metadata"]["uid"])
        assert client.pods.get("a")["spec"]["nodeName"] == "n1"
        lst = client.pods.list(field_selector="spec.nodeName=n1")
        assert len(lst["items"]) == 1
        client.pods.delete("a")
        with pytest.raises(errors.StatusError):
            client.pods.get("a")

    def test_status_and_patch(self, client):
        client.nodes.create({"apiVersion": "v1", "kind": "Node",
                             "metadata": {"name": "n1"},
                             "status": {"capacity": {"cpu": "4"}}})
        client.nodes.patch_status("n1", {"status": {"phase": "Running"}},
                                  namespace="")
        got = client.nodes.get("n1", namespace="")
        assert got["status"]["phase"] == "Running"
        assert got["status"]["capacity"]["cpu"] == "4"

    def test_watch_via_client(self, client):
        w = client.pods.watch(namespace="default")
        time.sleep(0.2)
        client.pods.create(mkpod("w1"))
        ev = w.next(timeout=5)
        assert ev is not None and ev.type == "ADDED"
        assert ev.object["metadata"]["name"] == "w1"
        w.stop()


class TestInformer:
    def test_sync_dispatch_and_index(self, api):
        client = Client.local(api)
        client.pods.create(mkpod("pre", node="n1"))
        adds, updates, deletes = [], [], []
        inf = SharedInformer(client.pods,
                             index_fns={"byNode": pods_by_node_index})
        inf.add_handlers(
            on_add=lambda o: adds.append(o["metadata"]["name"]),
            on_update=lambda o, n: updates.append(n["metadata"]["name"]),
            on_delete=lambda o: deletes.append(o["metadata"]["name"]))
        inf.start()
        assert inf.wait_for_sync()
        assert adds == ["pre"]
        client.pods.create(mkpod("live", node="n1"))
        time.sleep(0.5)
        assert "live" in adds
        assert [p["metadata"]["name"] for p in
                inf.indexer.by_index("byNode", "n1")] == ["pre", "live"] or \
               sorted(p["metadata"]["name"] for p in
                      inf.indexer.by_index("byNode", "n1")) == ["live", "pre"]
        got = client.pods.get("live")
        got["metadata"]["labels"] = {"x": "1"}
        client.pods.update(got)
        time.sleep(0.5)
        assert "live" in updates
        client.pods.delete("pre")
        time.sleep(0.5)
        assert deletes == ["pre"]
        assert inf.lister.get("default", "pre") is None
        inf.stop()

    def test_events_behind_a_waiting_handler_say_how_long_they_waited(
            self, api):
        """One thread delivers in turn (ISSUE 47): while a handler waits,
        the later events sit in the stream's buffer (`buffered`), and each
        then tells its handler how long ago it reached the informer
        (`delivery_lag`), which is nothing outside a watch dispatch."""
        client = Client.local(api)
        client.pods.create(mkpod("pre"))
        gate, lags = threading.Event(), {}

        def on_add(o):
            name = o["metadata"]["name"]
            lags[name] = inf.delivery_lag()
            if name == "first":
                gate.wait(10)       # as behind a lock a wave holds

        inf = SharedInformer(client.pods)
        inf.add_handlers(on_add=on_add)
        inf.start()
        assert inf.wait_for_sync()
        assert lags == {"pre": 0.0} and inf.buffered() == 0
        client.pods.create(mkpod("first"))
        client.pods.create(mkpod("second"))
        deadline = time.monotonic() + 10
        while inf.buffered() < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert inf.buffered() == 1 and "second" not in lags
        time.sleep(0.2)
        gate.set()
        while "second" not in lags and time.monotonic() < deadline:
            time.sleep(0.01)
        assert lags["second"] >= 0.2 > lags["first"]
        assert inf.delivery_lag() == 0.0
        inf.stop()

    def test_relist_after_stream_end(self, api):
        client = Client.local(api)
        inf = SharedInformer(client.pods, relist_backoff=0.1)
        inf.start()
        assert inf.wait_for_sync()
        # kill the live watch; the reflector must relist and keep going
        wait_for_watch(inf).stop()
        time.sleep(0.5)
        client.pods.create(mkpod("after-relist"))
        time.sleep(0.8)
        assert inf.lister.get("default", "after-relist") is not None
        inf.stop()

    def test_factory_shares_informers(self, api):
        client = Client.local(api)
        f = InformerFactory(client)
        a = f.informer("pods")
        b = f.informer("pods")
        assert a is b
        f.start()
        assert f.wait_for_sync()
        f.stop()


class TestWorkQueue:
    def test_dedup_and_done_requeue(self):
        q = WorkQueue()
        q.add("a")
        q.add("a")  # dedup while queued
        assert len(q) == 1
        item = q.get(timeout=1)
        assert item == "a"
        q.add("a")  # re-added while processing → dirty
        assert len(q) == 0
        q.done("a")  # returns to queue
        assert q.get(timeout=1) == "a"
        q.done("a")
        q.shutdown()
        assert q.get(timeout=0.1) is None

    def test_rate_limited_backoff_grows(self):
        q = RateLimitingQueue()
        t0 = time.monotonic()
        q.add_rate_limited("x")  # 5ms
        assert q.get(timeout=2) == "x"
        q.done("x")
        assert q.num_requeues("x") == 1
        q.forget("x")
        assert q.num_requeues("x") == 0
        q.shutdown()

    def test_add_after_delays(self):
        q = RateLimitingQueue()
        q.add_after("slow", 0.3)
        t0 = time.monotonic()
        assert q.get(timeout=3) == "slow"
        assert time.monotonic() - t0 >= 0.2
        q.shutdown()


class TestLeaderElection:
    def test_single_leader_and_failover(self, api):
        client = Client.local(api)
        events = []

        def mk(ident):
            return LeaderElector(client, LeaderElectionConfig(
                lock_name="sched", identity=ident,
                lease_duration=0.8, renew_deadline=0.5, retry_period=0.1,
                on_started_leading=lambda: events.append(("up", ident)),
                on_stopped_leading=lambda: events.append(("down", ident))))

        a, b = mk("a"), mk("b")
        a.start()
        assert a.wait_for_leadership(5)
        b.start()
        time.sleep(0.5)
        assert not b.is_leader  # live lease blocks b
        a.stop()  # a stops renewing; b must take over after expiry
        assert b.wait_for_leadership(5)
        assert ("up", "a") in events and ("up", "b") in events
        b.stop()


class TestEvents:
    def test_record_and_aggregate(self, api):
        client = Client.local(api)
        rec = EventRecorder(client, component="scheduler")
        pod = client.pods.create(mkpod("evt"))
        rec.event(pod, "Warning", "FailedScheduling", "0/3 nodes available")
        rec.event(pod, "Warning", "FailedScheduling", "0/3 nodes available")
        evs = client.events.list("default")["items"]
        assert len(evs) == 1
        assert evs[0]["count"] == 2
        assert evs[0]["reason"] == "FailedScheduling"
        assert evs[0]["source"]["component"] == "scheduler"


class _GatedEvents:
    """A client whose Event creates wait at a gate (open it to let the sink
    write), a broadcaster on it, and what the broadcaster's counter would
    read. `hold_sink()` parks the sink thread at the gate on a plug Event,
    so that whatever is queued next stays queued."""

    def __init__(self, api):
        self.client = Client.local(api)
        self.gate = threading.Event()
        self.writers = []       # idents of the threads that wrote an Event
        self.outcomes = []
        create = self.client.events.create

        def held(obj, ns=None):
            self.writers.append(threading.get_ident())
            assert self.gate.wait(10)
            return create(obj, ns)

        self.client.events.create = held
        self.broadcaster = EventBroadcaster(
            self.client, component="scheduler",
            observe=lambda outcome, n: self.outcomes.extend([outcome] * n))

    def hold_sink(self):
        self.broadcaster.event(mkpod("plug"), "Normal", "Plug", "held")
        deadline = time.monotonic() + 5
        while not self.writers and time.monotonic() < deadline:
            time.sleep(0.005)
        assert self.writers and self.broadcaster.pending() == 1

    def written(self):
        return [e for e in self.client.events.list("default")["items"]
                if e["reason"] != "Plug"]


@pytest.fixture
def gated(api):
    g = _GatedEvents(api)
    yield g
    g.gate.set()
    g.broadcaster.stop(timeout=5)


class TestEventBroadcaster:
    @pytest.mark.parametrize("pods, repeats, batched", [
        (1, 1, False), (40, 1, False), (40, 1, True), (1, 2, False),
        (3, 3, False), (3, 3, True)])
    def test_event_returns_at_once_and_the_sink_writes_later(
            self, gated, pods, repeats, batched):
        b = gated.broadcaster
        gated.hold_sink()
        objs = [gated.client.pods.create(mkpod(f"ev{i}"))
                for i in range(pods)]
        for _ in range(repeats):
            if batched:     # a wave's failed pods, under one lock hold
                b.events(objs, "Warning", "FailedScheduling", "0/3 nodes")
                continue
            for obj in objs:
                b.event(obj, "Warning", "FailedScheduling", "0/3 nodes")
        # back on the caller's thread with nothing written: one queue entry
        # a key, and no apiserver call from this thread
        assert b.pending() == 1 + pods
        assert gated.written() == []
        assert threading.get_ident() not in gated.writers
        assert not b.flush(timeout=0.05)
        gated.gate.set()
        assert b.flush(timeout=10) and b.pending() == 0
        evs = gated.written()
        assert sorted(e["involvedObject"]["name"] for e in evs) == \
            sorted(f"ev{i}" for i in range(pods))
        assert {e["count"] for e in evs} == {repeats}
        assert all(e["involvedObject"]["uid"] and e["type"] == "Warning"
                   and e["source"]["component"] == "scheduler" for e in evs)
        counts = {o: gated.outcomes.count(o) for o in set(gated.outcomes)}
        assert counts == {"queued": 1 + pods, "emitted": 1 + pods,
                          **({"coalesced": pods * (repeats - 1)}
                             if repeats > 1 else {})}

    def test_a_repeat_after_the_write_bumps_the_same_object(self, gated):
        b = gated.broadcaster
        gated.gate.set()
        pod = gated.client.pods.create(mkpod("again"))
        for _ in range(2):
            b.event(pod, "Warning", "FailedScheduling", "0/3 nodes")
            assert b.flush(timeout=10)
        (ev,) = gated.written()
        assert ev["count"] == 2

    def test_past_the_bound_events_are_dropped_and_nothing_blocks(
            self, gated, monkeypatch):
        monkeypatch.setattr(EventBroadcaster, "QUEUE_BOUND", 8)
        b = gated.broadcaster
        gated.hold_sink()
        t0 = time.monotonic()
        b.events([mkpod(f"over{i}") for i in range(13)], "Warning",
                 "FailedScheduling", "full")
        assert time.monotonic() - t0 < 1.0
        assert gated.outcomes.count("dropped") == 5
        assert b.pending() == 1 + 8
        # a repeat of a queued key still coalesces at the bound
        b.event(mkpod("over0"), "Warning", "FailedScheduling", "full")
        assert gated.outcomes.count("dropped") == 5
        gated.gate.set()
        assert b.flush(timeout=10)
        assert sorted(e["involvedObject"]["name"] for e in gated.written()) \
            == [f"over{i}" for i in range(8)]
        assert gated.outcomes.count("emitted") == 1 + 8

    def test_stop_counts_what_it_could_not_write_as_dropped(self, gated):
        b = gated.broadcaster
        gated.hold_sink()
        for i in range(3):
            b.event(mkpod(f"late{i}"), "Warning", "FailedScheduling", "x")
        b.stop(timeout=0.05)         # the sink is still held at the gate
        assert gated.outcomes.count("dropped") == 3 and b.pending() <= 1
        gated.gate.set()
        assert b.flush(timeout=10) and gated.written() == []

    def test_an_idle_sink_thread_sleeps_without_a_timeout(self, gated):
        b = gated.broadcaster
        waits = []
        wait = b._work.wait

        def counted(timeout=None):
            waits.append(timeout)
            return wait(timeout)

        b._work.wait = counted
        gated.gate.set()
        assert b._thread is None     # no Event yet: no thread at all
        b.event(mkpod("one"), "Normal", "Scheduled", "ok")
        assert b.flush(timeout=10)
        sink = b._thread
        time.sleep(0.3)
        # one wait since the queue emptied, never timed, still asleep in it
        assert waits == [None] and sink.is_alive()
        b.stop(timeout=5)
        assert not sink.is_alive() and b._thread is None


class TestInformerFactoryKeys:
    def test_namespace_scoped_informers_not_conflated(self, api):
        client = Client.local(api)
        client.pods.create(mkpod("in-default"))
        f = InformerFactory(client)
        scoped = f.informer("pods", namespace="kube-system")
        unscoped = f.informer("pods")
        assert scoped is not unscoped
        f.start()
        assert f.wait_for_sync()
        assert unscoped.lister.get("default", "in-default") is not None
        assert scoped.lister.get("default", "in-default") is None
        f.stop()

    def test_late_index_fns_backfilled(self, api):
        client = Client.local(api)
        client.pods.create(mkpod("idx", node="n9"))
        f = InformerFactory(client)
        f.informer("pods")
        f.start()
        assert f.wait_for_sync()
        inf = f.informer("pods", index_fns={"byNode": pods_by_node_index})
        got = inf.indexer.by_index("byNode", "n9")
        assert [p["metadata"]["name"] for p in got] == ["idx"]
        f.stop()


class TestRelistTombstones:
    def test_delete_during_relist_carries_last_known_object(self, api):
        client = Client.local(api)
        client.pods.create(mkpod("t1", labels={"app": "x"}))
        inf = SharedInformer(client.pods, relist_backoff=0.1)
        deletes = []
        inf.add_handlers(on_delete=lambda o: deletes.append(o))
        inf.start()
        assert inf.wait_for_sync()
        # kill the watch, delete while the informer is blind, let it relist
        wait_for_watch(inf).stop()
        client.pods.delete("t1")
        time.sleep(1.0)
        assert deletes, "relist did not synthesize the delete"
        assert deletes[-1].get("metadata", {}).get("labels") == {"app": "x"}
        inf.stop()


class TestControllerRestart:
    def test_controller_revives_after_stop(self, api):
        from kubernetes_tpu.controllers import ReplicaSetController
        from kubernetes_tpu.client import InformerFactory
        client = Client.local(api)
        f = InformerFactory(client)
        c = ReplicaSetController(client, f)
        f.start()
        f.wait_for_sync()
        c.start()
        c.stop()
        c.start()  # leadership regained: workers must serve again
        rs = {"apiVersion": "apps/v1", "kind": "ReplicaSet",
              "metadata": {"name": "revive", "namespace": "default"},
              "spec": {"replicas": 1,
                       "selector": {"matchLabels": {"app": "revive"}},
                       "template": {"metadata": {"labels": {"app": "revive"}},
                                    "spec": {"containers": [{"name": "c", "image": "i"}]}}}}
        client.replicasets.create(rs)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if len(client.pods.list("default",
                                    label_selector="app=revive")["items"]) == 1:
                break
            time.sleep(0.1)
        assert len(client.pods.list("default",
                                    label_selector="app=revive")["items"]) == 1
        c.stop()
        f.stop()


class TestEventRecreate:
    def test_event_recreated_after_server_side_delete(self, api):
        client = Client.local(api)
        rec = EventRecorder(client)
        pod = client.pods.create(mkpod("edel"))
        rec.event(pod, "Warning", "X", "msg")
        name = client.events.list("default")["items"][0]["metadata"]["name"]
        client.events.delete(name, "default")
        rec.event(pod, "Warning", "X", "msg")
        evs = client.events.list("default")["items"]
        assert len(evs) == 1 and evs[0]["count"] == 1
