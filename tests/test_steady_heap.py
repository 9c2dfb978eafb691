"""The serving processes' heap policy and the counter that says whether it
engages (ISSUE 33; utils/platform.py steady_heap, the wave record's
`minor_faults`, docs/OBSERVABILITY.md "Heap policy").

`steady_heap()` changes the process for good, so everything here but one
case drives it against a recording stand-in for glibc's `mallopt`.
"""

import json
import os
import re
import threading
import time

import pytest

from kubernetes_tpu.api.types import Pod, Resources
from kubernetes_tpu.models.workloads import make_nodes
from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler
from kubernetes_tpu.utils import platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the values PERF.md's section 7 measured (PR 31): the ceiling of each
MEASURED = {"trim_threshold": 256 << 20, "top_pad": 64 << 20,
            "mmap_threshold": 32 << 20}


class _Libc:
    """A recording stand-in for glibc: `mallopt` answers from `refuse`,
    `malloc` hands out 1, 2, 3, ... as addresses."""

    def __init__(self):
        self.set, self.refuse, self.blocks = [], set(), []

    def mallopt(self, param, value):
        self.set.append((param, value))
        return 0 if param in self.refuse else 1

    def malloc(self, size):
        self.blocks.append(("malloc", size))
        return sum(op == "malloc" for op, _ in self.blocks)

    def free(self, block):
        self.blocks.append(("free", block))


@pytest.fixture
def fresh(monkeypatch):
    """A process that has not set its policy yet, on a thread whose arena
    has not been given its room, over the recording libc."""
    libc = _Libc()
    monkeypatch.setattr(platform, "_heap_set", None)
    monkeypatch.setattr(platform, "_widened", threading.local())
    monkeypatch.setattr(platform, "_libc", lambda: libc)
    return libc


def test_it_sets_three_thresholds_once_and_reports_them(fresh):
    first = platform.steady_heap()
    assert platform.steady_heap() == first and len(fresh.set) == 3
    # glibc's M_TRIM_THRESHOLD, M_TOP_PAD, M_MMAP_THRESHOLD
    assert dict(fresh.set) == {-1: first["trim_threshold"],
                               -2: first["top_pad"],
                               -3: first["mmap_threshold"]}
    assert set(first) == set(MEASURED)
    assert all(0 < first[k] <= MEASURED[k] for k in MEASURED)
    # a new heap of a thread's arena is writable whole only if the pad is
    # a whole heap (glibc's HEAP_MAX_SIZE, 64 MiB)
    assert first["top_pad"] == 64 << 20
    first.clear()   # the caller's copy: the record is the module's
    assert set(platform.steady_heap()) == set(MEASURED)


def test_each_thread_that_calls_gets_room_in_its_arena_once(fresh):
    """Two blocks under the mmap threshold (over it they would be mapped
    apart from the arena), both held before either is given back."""
    policy = platform.steady_heap()
    size = fresh.blocks[0][1]
    assert fresh.blocks == [("malloc", size), ("malloc", size),
                            ("free", 2), ("free", 1)]
    assert policy["mmap_threshold"] // 2 < size < policy["mmap_threshold"]
    platform.steady_heap()
    assert len(fresh.blocks) == 4, "once a thread"
    t = threading.Thread(target=platform.steady_heap)
    t.start()
    t.join(10)
    assert not t.is_alive() and len(fresh.blocks) == 8
    assert len(fresh.set) == 3, "the process's policy is set once"


def test_a_value_the_libc_refuses_is_left_out_of_the_report(fresh):
    fresh.refuse.add(-3)
    assert set(platform.steady_heap()) == {"trim_threshold", "top_pad"}
    assert not fresh.blocks, "no room is made under a policy that is not"


class _NoMallopt:
    """A libc with no `mallopt` (musl): the attribute lookup fails."""


def _cdll_raises(name):
    raise OSError("no such library")


@pytest.mark.parametrize("cdll", [_cdll_raises, lambda name: _NoMallopt()],
                         ids=["no-libc", "no-symbol"])
def test_without_mallopt_it_is_a_no_op_that_says_so(monkeypatch, cdll):
    monkeypatch.setattr(platform, "_heap_set", None)
    monkeypatch.setattr(platform, "_widened", threading.local())
    monkeypatch.setattr(platform.ctypes, "CDLL", cdll)
    assert platform.steady_heap() == {}
    assert platform.steady_heap() == {}


def test_on_this_libc_it_engages_or_says_it_did_not():
    """The real call, as the tests' own APIServers have made it already:
    glibc takes all three, anything else none."""
    got = platform.steady_heap()
    assert got == platform.steady_heap()
    assert set(got) in (set(), set(MEASURED))


# --------------------------------------------------------------------- #
# who calls it
# --------------------------------------------------------------------- #


def _apiserver(calls):
    from kubernetes_tpu.apiserver import APIServer

    return APIServer().close


def _scheduler_server(calls):
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.sched.server import APIBinder, SchedulerServer

    api = APIServer()
    client = Client.local(api)
    srv = SchedulerServer(
        client, scheduler=Scheduler(binder=APIBinder(client)))
    calls.clear()   # the apiserver's own call is not the scheduler's
    srv.start()
    return lambda: (srv.stop(), api.close())


def _fleet_server(calls):
    from kubernetes_tpu.fleet.server import FleetServer

    FleetServer(batch_size=8)
    return lambda: None


def _extender_server(calls):
    from kubernetes_tpu.extender.backend import ExtenderBackend
    from kubernetes_tpu.extender.server import ExtenderServer

    return ExtenderServer(ExtenderBackend()).start().stop


@pytest.mark.parametrize("serve", [_apiserver, _scheduler_server,
                                   _fleet_server, _extender_server],
                         ids=lambda f: f.__name__.strip("_"))
def test_each_serving_process_sets_it_at_its_start(monkeypatch, serve):
    calls = []
    monkeypatch.setattr(platform, "steady_heap",
                        lambda: calls.append(1) or {})
    close = serve(calls)
    try:
        assert calls, f"{serve.__name__} never set the heap's policy"
    finally:
        close()


def test_the_thread_that_commits_the_waves_calls_it_itself(monkeypatch):
    """An arena is a thread's: the room `SchedulerServer.start()` makes on
    the caller's thread is not the loop's."""
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.sched.server import APIBinder, SchedulerServer

    callers = []
    monkeypatch.setattr(
        platform, "steady_heap",
        lambda: callers.append(threading.current_thread().name) or {})
    api = APIServer()
    client = Client.local(api)
    srv = SchedulerServer(
        client, scheduler=Scheduler(binder=APIBinder(client))).start()
    try:
        deadline = time.monotonic() + 10
        while "scheduler-loop" not in callers \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert "scheduler-loop" in callers
    finally:
        srv.stop()
        api.close()


def test_nothing_else_reaches_the_heap_policy():
    """No environment variable, argument or configuration field: the
    constants live in the one function (ISSUE 33's acceptance)."""
    found = []
    for d, _dirs, files in os.walk(os.path.join(ROOT, "kubernetes_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if re.search(r"MALLOC_|mallopt", fh.read()):
                        found.append(f)
    assert found == ["platform.py"]


# --------------------------------------------------------------------- #
# the counter on the wave's record
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("cpu, bound", [("10m", 6), ("64", 0)],
                         ids=["binds", "binds-nothing"])
def test_a_waves_record_carries_its_minor_faults(cpu, bound):
    s = Scheduler(binder=RecordingBinder(), batch_size=64)
    for n in make_nodes(4):
        s.on_node_add(n)
    for i in range(6):
        s.on_pod_add(Pod(name=f"p{i}", creation_index=i,
                         requests=Resources.make(cpu=cpu, memory="8Mi")))
    stats = s.schedule_pending()
    assert (stats.attempted, stats.scheduled) == (6, bound)
    rec = s.telemetry.recorder.records()[-1]
    assert rec["stats"]["scheduled"] == bound
    assert isinstance(rec["minor_faults"], int) and rec["minor_faults"] >= 0


def test_the_metric_is_data_over_that_field():
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           "minor_faults_per_pod.json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "minor_faults_per_pod")
    assert spec["source"] == {"kind": "waits", "field": "minor_faults"}
    assert spec["reduce"] == "per_bound_pod"
    assert spec["layer"] == entry["layer"] == "wave to apiserver + store"
    with open(os.path.join(ROOT, "PERF.md")) as f:
        assert "| wave to apiserver + store |" in f.read()
    moved = next(e for e in bench["end_to_end"]
                 if e["name"] == entry["moves"])
    assert entry["moves"] == "drain_pods_per_s"
    # every drain cell whose record is a scheduler's wave (the extender
    # cell's per-pod record, ISSUE 34, carries no `minor_faults`)
    assert set(entry["workloads"]) == set(moved["workloads"]) - {
        "extender-5k.filter-prioritize"}
