"""Where the interpreter's cyclic collector stands while a server lists the
cluster (ISSUE 50; utils/platform.py listing_heap, sched/server.py
initial_lists, docs/OBSERVABILITY.md "The collector at the start").

The first cases drive the context against a recording stand-in for the `gc`
module; the rest run the real collector, made eager so that a start at the
rehearsal size would be walked several times if the collector were on.
"""

import contextlib
import gc
import os
import re
import threading
import time

import pytest

from kubernetes_tpu.machinery import watch as mwatch
from kubernetes_tpu.sched.metrics import START_FROZEN
from kubernetes_tpu.sched.telemetry import gc_account
from kubernetes_tpu.utils import platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Gc:
    """A recording stand-in for the `gc` module: a freeze moves `made`
    objects to the frozen count."""

    def __init__(self, enabled=True):
        self.enabled, self.calls, self.frozen, self.made = enabled, [], 0, 0

    def isenabled(self):
        return self.enabled

    def disable(self):
        self.calls.append("disable")
        self.enabled = False

    def enable(self):
        self.calls.append("enable")
        self.enabled = True

    def freeze(self):
        self.calls.append("freeze")
        self.frozen, self.made = self.frozen + self.made, 0

    def get_freeze_count(self):
        return self.frozen

    def get_count(self):
        return (self.made, 0, 0)


@pytest.fixture
def fake(monkeypatch):
    fake = _Gc()
    monkeypatch.setattr(platform, "gc", fake)
    return fake


def test_the_collector_is_off_inside_and_back_after(fake):
    with platform.listing_heap() as took:
        assert not fake.enabled and took == {}
        fake.made = 7
    assert fake.enabled
    assert fake.calls == ["disable", "freeze", "enable"]
    assert took["frozen_objects"] == 7 and took["collector_off_s"] >= 0


def test_a_collector_found_off_is_left_off(fake):
    fake.enabled = False
    with platform.listing_heap():
        assert not fake.enabled
    assert not fake.enabled and fake.calls == ["disable", "freeze"]


def test_nested_contexts_freeze_once_at_the_last_exit(fake):
    with platform.listing_heap() as outer:
        with platform.listing_heap() as inner:
            fake.made = 3
        assert fake.calls == ["disable"] and not fake.enabled
        assert inner["frozen_objects"] == 0
        fake.made += 2
    assert fake.calls == ["disable", "freeze", "enable"]
    assert outer["frozen_objects"] == 5


def test_two_threads_freeze_once_when_the_last_of_them_leaves(fake):
    """A warm-up server and the measured one, or two servers of a test:
    whichever leaves last freezes, and only it restores."""
    inside, leave = threading.Event(), threading.Event()

    def other():
        with platform.listing_heap():
            inside.set()
            assert leave.wait(10)

    t = threading.Thread(target=other)
    with platform.listing_heap():
        t.start()
        assert inside.wait(10)
    # this thread was first in and is out; the other still lists
    assert fake.calls == ["disable"] and not fake.enabled
    leave.set()
    t.join(10)
    assert not t.is_alive()
    assert fake.calls == ["disable", "freeze", "enable"] and fake.enabled


def test_an_exception_inside_still_freezes_and_restores(fake):
    with pytest.raises(RuntimeError):
        with platform.listing_heap():
            raise RuntimeError("a sync that raised")
    assert fake.enabled and fake.calls == ["disable", "freeze", "enable"]
    with platform.listing_heap():   # and the depth is back at none open
        assert not fake.enabled
    assert fake.calls[3:] == ["disable", "freeze", "enable"]


def test_what_is_made_inside_leaves_the_collectors_walk():
    """The real collector: the objects made inside are frozen, none of them
    was walked on the way, and refcounts still free them."""
    assert gc.isenabled()
    full = gc_account().full.count
    with platform.listing_heap() as took:
        assert not gc.isenabled()
        kept = [[str(i)] for i in range(20_000)]
    assert gc.isenabled()
    assert took["frozen_objects"] >= len(kept)
    assert gc_account().full.count == full
    frozen = gc.get_freeze_count()
    del kept[:]   # acyclic: the last reference frees each where it drops
    assert gc.get_freeze_count() <= frozen - 20_000


def test_nothing_else_turns_the_collector_off():
    """No environment variable, argument or configuration field, and no
    other caller of `gc.disable()` / `gc.freeze()` in the program (ISSUE
    50's acceptance)."""
    found = []
    for d, _dirs, files in os.walk(os.path.join(ROOT, "kubernetes_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if re.search(r"gc\.(disable|freeze)\(", fh.read()):
                        found.append(f)
    assert found == ["platform.py"]


# --------------------------------------------------------------------- #
# the two starts, at the rehearsal size
# --------------------------------------------------------------------- #


@contextlib.contextmanager
def eager_collector():
    """A collector that would walk a small start again and again: nothing
    old to hide behind (the "a quarter more than last time" rule counts
    from an empty old generation, as it does after the benchmark's set-up)
    and a full collection every ~3,000 containers."""
    thresholds = gc.get_threshold()
    gc.freeze()
    gc.collect()
    gc.set_threshold(700, 2, 2)
    try:
        yield
    finally:
        gc.set_threshold(*thresholds)
        gc.unfreeze()


def _start_stages(loop):
    """{stage: whether a collection ran inside it} of a record's `loop`."""
    stages = [p for p in loop["children"]
              if p.startswith("start/") and p.count("/") == 1]
    return {s: f"{s}/gc" in loop["children"] for s in stages}


def _wait(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not cond():
        time.sleep(0.01)
    return cond()


def test_a_schedulers_start_lists_with_the_collector_off():
    from test_extender import SEEDED

    from benchmarks.harness import objects
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.sched.scheduler import Scheduler
    from kubernetes_tpu.sched.server import APIBinder, SchedulerServer

    api = APIServer()
    client = Client.local(api)
    groups = objects.Groups(SEEDED, 7, 256 // SEEDED["groups"])
    for n in objects.make_nodes(SEEDED):
        client.nodes.create(n)
    for p in objects.prebound_pods(groups, SEEDED["nodes"], 256):
        client.pods.create(p)
    client.pods.create(groups.pod(0, "pending"))
    srv = SchedulerServer(
        client, scheduler=Scheduler(binder=APIBinder(client)))
    tel = srv.scheduler.telemetry
    with eager_collector():
        frozen = gc.get_freeze_count()
        try:
            srv.start()
            assert gc.isenabled()
            assert gc.get_freeze_count() > frozen + 256
            assert _wait(lambda: any(
                "loop" in r for r in tel.recorder.records()))
        finally:
            srv.stop()
            api.close()
    loop = next(r["loop"] for r in tel.recorder.records() if "loop" in r)
    stages = _start_stages(loop)
    stages.pop("start/wiring")   # after the lists: the collector's again
    assert stages == {"start/volumes-sync": False, "start/nodes-sync": False,
                      "start/pods-sync": False}
    assert loop["children"]["start/pods-sync/handlers/decode"][0] == 257
    assert loop["start_frozen_objects"] > 256
    assert 0 < loop["start_collector_off_s"] <= \
        dict(map(tuple, loop["phases"]))["start"]
    assert START_FROZEN.value(component="scheduler") == \
        loop["start_frozen_objects"]


def test_a_served_extenders_start_lists_and_compiles_with_the_collector_off():
    from test_extender import _post, _role_group, served_cluster

    with eager_collector():
        frozen = gc.get_freeze_count()
        with served_cluster(bound=256) as (client, served, groups):
            assert gc.isenabled()
            assert gc.get_freeze_count() > frozen + 256
            v1 = client.pods.create(
                groups.pod(_role_group(groups, "plain"), "first"))
            _post(served.url, "filter", {
                "Pod": v1, "NodeNames": [f"node-{i}" for i in range(64)]})
            served.backend.flush_record()
            first = served.backend.telemetry.recorder.records()[0]
    loop = first["loop"]
    stages = _start_stages(loop)
    stages.pop("start/socket")   # after the lists: the collector's again
    assert stages == {"start/nodes-sync": False, "start/pods-sync": False,
                      "start/compile-ahead": False}
    assert loop["start_frozen_objects"] > 256
    assert 0 < loop["start_collector_off_s"] <= loop["phases"][0][1]
    assert START_FROZEN.value(component="extender") == \
        loop["start_frozen_objects"]
    # the account `start_log` reads is stages alone
    assert [name for name, _s in served.start_log] == [
        "nodes-sync", "pods-sync", "compile-ahead", "socket"]


def test_a_relist_in_a_running_server_leaves_the_collector_on():
    """A broken watch's relist is no start: the collector stays on, and
    nothing more is frozen."""
    from kubernetes_tpu.client import SharedInformer
    from kubernetes_tpu.sched.server import initial_lists, start_informer
    from kubernetes_tpu.sched.telemetry import SchedulerTelemetry

    watches = []

    class _Pods:
        group, resource = "", "pods"

        def list(self, *a, **k):
            return {"items": [{"metadata": {"name": "p", "namespace": "d"}}],
                    "metadata": {"resourceVersion": "1"}}

        def watch(self, *a, **k):
            watches.append(mwatch.Watch(capacity=4))
            return watches[-1]

    seen = []
    inf = SharedInformer(_Pods(), relist_backoff=0.01)
    inf.add_handlers(on_add=lambda o: seen.append(gc.isenabled()),
                     on_update=lambda o, n: seen.append(gc.isenabled()))
    tel = SchedulerTelemetry()
    tel.loop_reset()
    try:
        with initial_lists(tel, "scheduler"):
            assert start_informer(inf, tel, "start/pods-sync", "scheduler")
        assert seen == [False] and gc.isenabled()
        frozen = gc.get_freeze_count()
        assert _wait(lambda: watches)
        watches[0].terminate(mwatch.Event(mwatch.ERROR, {"code": 410}))
        assert _wait(lambda: inf.relists == 2 and len(seen) == 2)
        assert seen == [False, True] and gc.isenabled()
        assert gc.get_freeze_count() <= frozen
    finally:
        inf.stop()
