"""Fleet watch plane (ISSUE 13).

Covers, bottom-up:

  * machinery/watch.py — terminal-event delivery after drain (the vehicle
    for too-old/restart Status frames on bounded channels);
  * storage/store.py — per-watcher bounded buffers with deaf-consumer
    eviction (one watcher pays, the broadcast never stalls), BOOKMARK
    broadcasts on compaction-boundary crossings + the `watch.compact@floor`
    seam, and `drop_watchers` emitting a terminal 503 first;
  * client/informers.py — resume-by-RV on non-410 terminal errors, relist
    ONLY on a genuine 410 beneath the compaction floor, bookmark-funded
    resumes, RelistBackoff reset on ANY successful list+replace
    (satellite 1), and stop() interrupting the relist sleep (bounded join);
  * client/watchmux.py — one upstream stream fanned to per-tenant routes,
    late-join synthesis, slow-route eviction + indexer-snapshot resync
    (never an apiserver relist), sequence fencing, `watch.stall@<route>`
    and `mux.die@stream` seams;
  * fleet/server.py FleetWatchPlane — K tenants on 2 streams total,
    staleness export, mux death → serve-from-cache → revive-as-resume,
    and the compaction-storm drill: relists stay O(1) per genuine
    floor-crossing, not O(K) (satellite 3).
"""

import threading
import time

import pytest

from kubernetes_tpu.machinery import errors
from kubernetes_tpu.machinery import watch as mwatch
from kubernetes_tpu.storage.native import PyKV
from kubernetes_tpu.storage.store import Storage
from kubernetes_tpu.utils import faultline

pytestmark = pytest.mark.watchplane


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faultline.uninstall()


def v1pod(name, tenant=None, ns="default", cpu="100m"):
    labels = {"ktpu.io/tenant": tenant} if tenant else {}
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": ns, "labels": labels},
            "spec": {"containers": [{"name": "c", "image": "i",
                     "resources": {"requests": {"cpu": cpu,
                                                "memory": "64Mi"}}}]}}


def v1node(name, tenant=None, cpu="8"):
    labels = {"kubernetes.io/hostname": name}
    if tenant:
        labels["ktpu.io/tenant"] = tenant
    return {"apiVersion": "v1", "kind": "Node",
            "metadata": {"name": name, "labels": labels},
            "status": {"allocatable": {"cpu": cpu, "memory": "16Gi",
                                       "pods": "32"}}}


def wait_until(cond, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# --------------------------------------------------------------------- #
# machinery/watch.py: the bounded channel's terminal-event contract
# --------------------------------------------------------------------- #


class TestWatchChannel:
    def test_terminal_delivered_after_drain(self):
        w = mwatch.Watch(capacity=8)
        for i in range(3):
            w.send(mwatch.Event(mwatch.ADDED, {"i": i}))
        w.terminate(mwatch.Event(mwatch.ERROR, {"code": 410}))
        got = [w.next(timeout=1) for _ in range(4)]
        assert [e.type for e in got[:3]] == [mwatch.ADDED] * 3
        assert got[3].type == mwatch.ERROR and got[3].object["code"] == 410
        assert w.next(timeout=0.1) is None  # terminal delivered exactly once

    def test_terminal_survives_full_buffer(self):
        w = mwatch.Watch(capacity=2)
        assert w.send(mwatch.Event(mwatch.ADDED, {"i": 0}), timeout=0)
        assert w.send(mwatch.Event(mwatch.ADDED, {"i": 1}), timeout=0)
        # the buffer is full: a plain send fails (and stops the watch) —
        # but terminate() can still leave the WHY
        assert not w.send(mwatch.Event(mwatch.ADDED, {"i": 2}), timeout=0)
        w.terminate(mwatch.Event(mwatch.ERROR, {"code": 410}))
        types = []
        for ev in w:
            types.append(ev.type)
        assert types == [mwatch.ADDED, mwatch.ADDED, mwatch.ERROR]

    def test_depth(self):
        w = mwatch.Watch(capacity=8)
        assert w.depth() == 0
        w.send(mwatch.Event(mwatch.ADDED, {}))
        assert w.depth() == 1


# --------------------------------------------------------------------- #
# storage: deaf-watcher eviction + bookmark-on-compaction
# --------------------------------------------------------------------- #


class TestStorageWatchPlane:
    @pytest.fixture
    def st(self):
        st = Storage(kv=PyKV(), bookmark_interval=3600)
        yield st
        st.close()

    @pytest.mark.parametrize("compacted, code, word", [
        (False, 504, "resume from resourceVersion"),
        (True, 410, "too old")])
    def test_deaf_watcher_cut_off_resumably_unless_compacted(
            self, st, compacted, code, word):
        """A consumer that never reads is cut off once its buffer has been
        full for the deaf budget: with a Status it can RESUME from while
        the events it is owed exist, with 410 only beneath the floor."""
        st.deaf_after_s = 3600
        w = st.watch("/registry/pods/", buffer=4)
        for i in range(20):
            st.create(f"/registry/pods/default/p{i}",
                      {"metadata": {"name": f"p{i}"}})
        assert wait_until(lambda: st.dispatched_rev >= st.kv.rev(), 5)
        assert not w.stopped and st.deaf_evictions == 0  # late, not cut off
        if compacted:
            st.compact_to(st.kv.rev())
        st.deaf_after_s = 0.1
        assert wait_until(lambda: w.stopped, 5), "deaf watcher not evicted"
        assert st.deaf_evictions == 1
        # drain: the buffered events, then the terminal ERROR
        evs = []
        while True:
            ev = w.next(timeout=0.2)
            if ev is None:
                break
            evs.append(ev)
        assert [e.object["metadata"]["name"] for e in evs[:-1]] == \
            [f"p{i}" for i in range(4)], "buffered events lost"
        assert evs[-1].type == mwatch.ERROR
        assert evs[-1].object.get("code") == code
        assert word in evs[-1].object.get("message", "")
        rv = evs[-2].object["metadata"]["resourceVersion"]
        if compacted:
            with pytest.raises(errors.StatusError) as ei:
                st.watch("/registry/pods/", since_rv=rv)
            assert ei.value.code == 410
            return
        # the resume the Status pointed at: the other 16, once, in order
        w2 = st.watch("/registry/pods/", since_rv=rv, buffer=64)
        got = [w2.next(timeout=2).object["metadata"]["name"]
               for _ in range(16)]
        assert got == [f"p{i}" for i in range(4, 20)]
        assert w2.next(timeout=0.1) is None
        w2.stop()

    def test_broadcast_survives_deaf_sibling(self, st):
        st.deaf_after_s = 0.2
        deaf = st.watch("/registry/pods/", buffer=4)
        live = st.watch("/registry/pods/", buffer=1024)
        got = []
        t = threading.Thread(
            target=lambda: [got.append(e) for e in live], daemon=True)
        t.start()
        for i in range(50):
            st.create(f"/registry/pods/default/q{i}",
                      {"metadata": {"name": f"q{i}"}})
        assert wait_until(lambda: len(got) >= 50, 10), \
            f"live watcher starved behind deaf sibling: {len(got)}/50"
        assert wait_until(lambda: deaf.stopped, 5) \
            and st.deaf_evictions >= 1
        live.stop()
        t.join(timeout=3)

    @pytest.mark.parametrize("source", ["ring", "log"])
    @pytest.mark.parametrize("kv", ["py", "native"])
    @pytest.mark.parametrize("start", ["live", "resume"])
    def test_burst_of_ten_buffers_arrives_late_never_less(
            self, kv, source, start):
        """A burst of 10 x the buffer while the consumer drains at its own
        pace: every event once, in order, nobody cut off, no 410 — from
        the cacher ring, and beneath its horizon from the KV log; for a
        watcher that was live through the burst and for one that resumes
        from before it (the catch-up comes in slices too)."""
        from kubernetes_tpu.storage import native
        from kubernetes_tpu.storage.cacher import WatchCache

        st = Storage(kv=PyKV() if kv == "py" else native.new_kv(),
                     watch_buffer=64, bookmark_interval=3600)
        try:
            if source == "log":
                st.watch_cache = WatchCache(capacity=16,
                                            horizon=st.dispatched_rev)
            st.create("/registry/nodes/n0", {"metadata": {"name": "n0"}})
            rv0 = str(st.kv.rev())   # "0" would mean "from now"
            read, other = st.watch_plane_reader(), st.watch_plane_reader()
            reads = []   # what each read of the KV log asked for and got
            kv_events_since = st.kv.events_since

            def counting(since, prefix="", limit=0):
                out = kv_events_since(since, prefix, limit)
                if prefix:   # a watcher's refill, not the pump's own read
                    reads.append((limit, len(out)))
                return out

            st.kv.events_since = counting
            caught_up = st.watch_cache.hits + st.watch_cache.storage_fallbacks
            fast = st.watch("/registry/pods/", buffer=4096)
            w = st.watch("/registry/pods/") if start == "live" else None
            for i in range(640):
                st.create(f"/registry/pods/default/b{i}",
                          {"metadata": {"name": f"b{i}"}})
            if w is None:
                assert wait_until(lambda: st.dispatched_rev >= st.kv.rev(), 5)
                w = st.watch("/registry/pods/", since_rv=rv0)
            got = []
            while len(got) < 640:
                ev = w.next(timeout=5)
                assert ev is not None, f"stalled at {len(got)}"
                assert ev.type != mwatch.ERROR, ev.object
                got.append(ev.object["metadata"]["name"])
                if len(got) % 50 == 0:
                    time.sleep(0.01)   # its own pace
            assert got == [f"b{i}" for i in range(640)]
            assert w.next(timeout=0.1) is None and not w.stopped
            assert st.deaf_evictions == 0
            # the sibling with room never waited for the slow one
            assert fast.depth() == 640
            # and the stream goes on where the catch-up ended
            st.create("/registry/pods/default/after",
                      {"metadata": {"name": "after"}})
            assert w.next(timeout=5).object["metadata"]["name"] == "after"
            # nine refills and more (a live watcher's first buffer may have
            # come by broadcast) are ONE catch-up on the cache's counters,
            # and beneath the ring's horizon none read more of the log than
            # the buffer had room for (and one, to know whether it is level)
            assert st.watch_cache.hits + st.watch_cache.storage_fallbacks \
                == caught_up + 1
            assert (len(reads) >= 9) == (source == "log")
            assert all(0 < limit <= 65 and n <= limit for limit, n in reads)
            # each reader keeps its own high-water mark of the pump's lag
            assert st.pump_batch_max >= 1
            first = read()
            assert first["watch_evictions"] == 0 and first["pump_lag_max"] >= 1
            assert read()["pump_lag_max"] == 0
            assert other()["pump_lag_max"] >= 1
        finally:
            st.close()

    def test_a_writer_that_outruns_the_pump_waits_for_it_bounded(self):
        """Flow control at the source: with the pump held back, a write
        past the high-water mark waits — once, at most PACE_WAIT_S: a pump
        that a whole wait saw stand still costs writers nothing more until
        it moves — and goes on at once when the pump is level again."""
        from kubernetes_tpu.storage import store as store_mod

        st = Storage(kv=PyKV(), watch_buffer=64, bookmark_interval=3600)
        try:
            assert st._pace_high == 8
            w = st.watch("/registry/pods/", buffer=1024)
            for episode in (1, 2):   # the pump moved in between: armed again
                took = []
                with st._watch_mu:   # the pump cannot broadcast
                    for i in range(12):
                        t0 = time.perf_counter()
                        st.create(f"/registry/pods/default/w{episode}-{i}",
                                  {"metadata": {"name": f"w{episode}-{i}"}})
                        took.append(time.perf_counter() - t0)
                assert st.paced_writes == episode        # the ninth write
                assert store_mod.PACE_WAIT_S * 0.9 <= took[8] < 1.0
                assert max(took[:8] + took[9:]) < store_mod.PACE_WAIT_S / 2
                assert wait_until(lambda: w.depth() == 12 * episode, 5)
            t0 = time.perf_counter()
            st.create("/registry/pods/default/level",
                      {"metadata": {"name": "level"}})
            assert time.perf_counter() - t0 < store_mod.PACE_WAIT_S / 2
            assert st.paced_writes == 2
        finally:
            st.close()

    @pytest.mark.parametrize("kv", ["py", "native"])
    def test_the_pump_pays_its_turn_once_for_many_events(self, kv):
        """ISSUE 28: N writes made while the pump is held back arrive as ONE
        turn, in order, and the reader says so (`pump_events` N,
        `pump_turns` 1, the pump thread's CPU seconds); a single write on
        a quiet store is broadcast with no added wait; under a stream of
        writes the pump lets them gather, and still loses none."""
        from kubernetes_tpu.storage import native
        from kubernetes_tpu.storage import store as store_mod

        st = Storage(kv=PyKV() if kv == "py" else native.new_kv(),
                     bookmark_interval=3600)
        try:
            gate, parked, gathers = threading.Event(), threading.Event(), []
            kv_wait, stop_wait = st.kv.wait, st._stop.wait

            def held(rev, timeout):
                if not gate.is_set():
                    parked.set()
                    gate.wait(10)
                return kv_wait(rev, timeout)

            def gathering(timeout):   # the pump's one sleep
                gathers.append(timeout)
                return stop_wait(timeout)

            st.kv.wait, st._stop.wait = held, gathering
            w = st.watch("/registry/pods/")
            read = st.watch_plane_reader()
            assert parked.wait(5), "the pump never came back to its wait"
            n = 100
            for i in range(n):
                st.create(f"/registry/pods/default/h{i}",
                          {"metadata": {"name": f"h{i}"}})
            assert w.depth() == 0 and st.dispatched_rev < st.kv.rev()
            gate.set()
            assert wait_until(lambda: w.depth() == n, 5)
            got = read()
            assert (got["pump_events"], got["pump_turns"]) == (n, 1)
            assert got["pump_busy_s"] > 0 and got["pump_lag_max"] == n
            assert [w.next(timeout=1).object["metadata"]["name"]
                    for _ in range(n)] == [f"h{i}" for i in range(n)]
            assert read()["pump_events"] == 0   # a baseline of its own

            # quiet store, one write: no gathering wait before its turn
            time.sleep(4 * store_mod.GATHER_S)
            del gathers[:]
            st.create("/registry/pods/default/single",
                      {"metadata": {"name": "single"}})
            assert w.next(timeout=2).object["metadata"]["name"] == "single"
            assert gathers == []
            assert read()["pump_turns"] == 1

            # a stream of writes: turns of many events, none lost
            time.sleep(4 * store_mod.GATHER_S)
            m = 300
            t0 = time.perf_counter()
            for i in range(m):
                st.create(f"/registry/pods/default/s{i}",
                          {"metadata": {"name": f"s{i}"}})
                time.sleep(0.0002)   # the pump is woken for every write
            apart = (time.perf_counter() - t0) / m
            assert [w.next(timeout=2).object["metadata"]["name"]
                    for _ in range(m)] == [f"s{i}" for i in range(m)]
            got = read()
            assert got["pump_events"] == m
            if apart < store_mod.GATHER_S / 4:   # the box kept the pace
                assert gathers and set(gathers) == {store_mod.GATHER_S}
                assert got["pump_turns"] < m / 3
            assert w.next(timeout=0.05) is None
        finally:
            gate.set()
            st.close()

    @pytest.mark.parametrize("kv", ["py", "native"])
    def test_many_writers_lose_duplicate_and_reorder_nothing(self, kv):
        """More writers than cores on a shortened switch interval, against
        a pump whose turns gather: a roomy stream, one with a predicate and
        one with a small buffer read at its own pace each receive every
        event once, in revision order, each object whole."""
        import sys

        from kubernetes_tpu.storage import native

        st = Storage(kv=PyKV() if kv == "py" else native.new_kv(),
                     watch_buffer=4096, bookmark_interval=3600)
        writers, per = 12, 60
        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            streams = {
                "roomy": st.watch("/registry/pods/"),
                "odd": st.watch("/registry/pods/",
                                predicate=lambda o: o["n"] % 2 == 1),
                "small": st.watch("/registry/pods/", buffer=32),
            }

            def write(t):
                key = f"/registry/pods/default/t{t}"
                st.create(key, {"metadata": {"name": f"t{t}"}, "n": 0})
                for _ in range(per - 1):
                    st.guaranteed_update(
                        key, lambda o: {**o, "n": o["n"] + 1})

            threads = [threading.Thread(target=write, args=(t,))
                       for t in range(writers)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
            assert not any(th.is_alive() for th in threads)
            total = writers * per
            for name, w in streams.items():
                want = total // 2 if name == "odd" else total
                revs, last_n = [], {}
                while len(revs) < want:
                    ev = w.next(timeout=10)
                    assert ev is not None, f"{name} stalled at {len(revs)}"
                    assert ev.type != mwatch.ERROR, ev.object
                    obj = ev.object
                    revs.append(int(obj["metadata"]["resourceVersion"]))
                    who = obj["metadata"]["name"]
                    step = 2 if name == "odd" else 1   # n counts from 0
                    assert obj["n"] == last_n.get(who, -1) + step
                    last_n[who] = obj["n"]
                assert revs == sorted(set(revs)), f"{name}: order or dups"
                assert w.next(timeout=0.1) is None
            assert st.deaf_evictions == 0
        finally:
            sys.setswitchinterval(was)
            st.close()

    def test_a_dead_pump_costs_writers_nothing(self):
        st = Storage(kv=PyKV(), watch_buffer=64, bookmark_interval=3600)
        try:
            st._stop.set()
            st._pump.join(timeout=2)
            assert not st._pump.is_alive()
            t0 = time.perf_counter()
            for i in range(100):
                st.create(f"/registry/pods/default/d{i}",
                          {"metadata": {"name": f"d{i}"}})
            assert time.perf_counter() - t0 < 0.05 * 100 / 10
            assert st.paced_writes == 0
        finally:
            st.close()

    def test_compaction_gap_costs_only_the_watcher_that_is_owed(self, st):
        """A real gap still gives 410: compaction takes events a lagging
        watcher had not been handed yet. It alone is sent to relist."""
        st.deaf_after_s = 3600
        slow = st.watch("/registry/pods/", buffer=4)
        live = st.watch("/registry/pods/", buffer=1024)
        for i in range(20):
            st.create(f"/registry/pods/default/g{i}",
                      {"metadata": {"name": f"g{i}"}})
        assert wait_until(lambda: live.depth() == 20, 5)
        st.compact_to(st.kv.rev())
        names = []
        while True:   # it drains what it holds, then learns of the gap
            ev = slow.next(timeout=2)
            assert ev is not None
            if ev.type == mwatch.ERROR:
                assert ev.object["code"] == 410
                break
            names.append(ev.object["metadata"]["name"])
        assert names == [f"g{i}" for i in range(4)]
        assert st.deaf_evictions == 0   # a gap is not deafness
        st.create("/registry/pods/default/later",
                  {"metadata": {"name": "later"}})
        assert wait_until(lambda: live.depth() == 21, 5) and not live.stopped

    def test_compaction_boundary_bookmark(self, st):
        wb = st.watch("/registry/pods/", bookmarks=True)
        plain = st.watch("/registry/pods/")
        for i in range(5):
            st.create(f"/registry/pods/default/c{i}",
                      {"metadata": {"name": f"c{i}"}})
        assert wait_until(
            lambda: st._dispatched_rev >= st.kv.rev(), 5)
        for _ in range(5):  # drain the creates
            wb.next(timeout=1)
        st.compact_to(st.kv.rev())
        # the boundary bookmark arrives IMMEDIATELY (interval is 1 h here)
        ev = wb.next(timeout=2)
        assert ev is not None and ev.type == mwatch.BOOKMARK
        rv = int(ev.object["metadata"]["resourceVersion"])
        assert rv >= st.kv.compacted_rev(), \
            "bookmark beneath the compaction floor cannot fund a resume"
        assert st.compaction_bookmarks >= 1
        # non-opted-in watcher: events only, no bookmark frame
        for _ in range(5):
            plain.next(timeout=0.5)
        assert plain.next(timeout=0.3) is None
        wb.stop()
        plain.stop()

    def test_watch_compact_floor_seam(self, st):
        # persistent (2+): the seam compacts at the PUMP'S dispatched rev,
        # which lags the kv head by up to one iteration — a one-shot could
        # fire while nothing has been dispatched yet and compact at 0
        faultline.install("watch.compact@floor:2+")
        wb = st.watch("/registry/pods/", bookmarks=True)
        st.create("/registry/pods/default/x", {"metadata": {"name": "x"}})
        assert wait_until(lambda: st.kv.compacted_rev() > 0, 10), \
            "seam never compacted"
        assert wait_until(lambda: st.compaction_bookmarks >= 1, 10)
        wb.stop()

    def test_drop_watchers_emits_terminal_503(self, st):
        w = st.watch("/registry/pods/")
        n = st.drop_watchers()
        assert n == 1
        ev = w.next(timeout=1)
        assert ev is not None and ev.type == mwatch.ERROR
        assert ev.object.get("code") == 503

    def test_apiserver_watch_buffer_param(self):
        from kubernetes_tpu.apiserver import APIServer

        api = APIServer(watch_buffer=7)
        try:
            assert api.storage._watch_buffer == 7
        finally:
            api.close()


# --------------------------------------------------------------------- #
# informer: resume vs relist discipline
# --------------------------------------------------------------------- #


def _mkapi():
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client

    api = APIServer()
    return api, Client.local(api)


class TestInformerResume:
    def test_restart_503_resumes_by_rv_not_relist(self):
        """Satellite 2: the apiserver-restart seam now emits a terminal
        ERROR Status, so informers resume from their resourceVersion —
        the blind-relist path (socket-EOF-only death) is gone."""
        from kubernetes_tpu.client import SharedInformer

        api, client = _mkapi()
        inf = SharedInformer(client.pods, namespace="default",
                             relist_backoff=0.02).start()
        try:
            assert inf.wait_for_sync(10)
            assert inf.relists == 1
            client.pods.create(v1pod("before"))
            assert wait_until(lambda: len(inf.indexer) == 1, 10)
            api.storage.drop_watchers()
            client.pods.create(v1pod("after"))
            assert wait_until(lambda: len(inf.indexer) == 2, 10), \
                "informer never recovered from the restart"
            assert inf.relists == 1, \
                "restart cost a relist — the 503 resume path regressed"
            assert inf.resumes >= 1
        finally:
            inf.stop()
            api.close()

    def test_genuine_410_relists_exactly_once(self):
        from kubernetes_tpu.client import SharedInformer
        from kubernetes_tpu.storage.cacher import WatchCache

        api, client = _mkapi()
        inf = SharedInformer(client.pods, namespace="default",
                             relist_backoff=0.02).start()
        try:
            assert inf.wait_for_sync(10)
            client.pods.create(v1pod("a"))
            assert wait_until(lambda: len(inf.indexer) == 1, 10)
            inf.stop()
            # while the informer is away: more writes, then a compaction
            # that buries its resume token beneath the floor (the cacher
            # ring is reset too, so there is no memory catch-up window)
            client.pods.create(v1pod("b"))
            st = api.storage
            st.compact_to(st.kv.rev())
            st.watch_cache = WatchCache(horizon=st.kv.rev())
            inf.start()
            assert wait_until(lambda: len(inf.indexer) == 2, 15), \
                "informer never converged after the 410"
            assert inf.relists == 2, \
                f"a genuine 410 must cost exactly one relist, saw " \
                f"{inf.relists - 1}"
        finally:
            inf.stop()
            api.close()

    def test_bookmark_funds_resume_on_quiet_stream(self, monkeypatch):
        """A quiet resource + compaction: the boundary bookmark advances
        the resume token, so a stream death later resumes cleanly —
        bookmark_resumes counts it."""
        from kubernetes_tpu.client import SharedInformer

        api, client = _mkapi()
        inf = SharedInformer(client.nodes, relist_backoff=0.02).start()
        try:
            assert inf.wait_for_sync(10)
            client.nodes.create(v1node("n0"))
            assert wait_until(lambda: len(inf.indexer) == 1, 10)
            # churn another resource, then compact: nodes saw NOTHING —
            # only the boundary bookmark keeps its token above the floor
            for i in range(5):
                client.pods.create(v1pod(f"churn-{i}"))
            st = api.storage
            assert wait_until(lambda: st._dispatched_rev >= st.kv.rev(), 5)
            st.compact_to(st.kv.rev())
            assert wait_until(lambda: inf.bookmarks_seen >= 1, 5), \
                "no bookmark reached the informer"
            assert wait_until(
                lambda: inf.last_sync_rv
                and int(inf.last_sync_rv) >= st.kv.compacted_rev(), 5)
            # now the stream dies (restart seam): resume must succeed from
            # the bookmarked RV — no relist, and the resume is
            # bookmark-funded
            st.drop_watchers()
            client.nodes.create(v1node("n1"))
            assert wait_until(lambda: len(inf.indexer) == 2, 10)
            assert inf.relists == 1
            assert inf.bookmark_resumes >= 1
        finally:
            inf.stop()
            api.close()


class _StubRC:
    """Minimal ResourceClient stand-in for reflector-loop unit tests."""

    group = ""
    resource = "stubs"

    def __init__(self, list_fn=None, watch_fn=None):
        self.lists = 0
        self.watches = 0
        self._list_fn = list_fn
        self._watch_fn = watch_fn

    def list(self, *a, **k):
        self.lists += 1
        if self._list_fn is not None:
            return self._list_fn()
        return {"items": [], "metadata": {"resourceVersion": "1"}}

    def watch(self, *a, **k):
        self.watches += 1
        if self._watch_fn is not None:
            return self._watch_fn()
        w = mwatch.Watch(capacity=4)
        w.terminate(mwatch.Event(mwatch.ERROR, {"code": 410}))
        return w


class TestRelistBackoffFix:
    def test_successful_list_collapses_decayed_ladder(self):
        """Satellite 1: a watch that dies right after a SUCCESSFUL list
        must not keep retrying at the decayed cap — every successful
        list+replace collapses the ladder to its first rung (the failure
        the backoff priced is over), while an instantly-410ing watch
        phase still can't drive relists at the raw base cadence."""
        from kubernetes_tpu.client import SharedInformer

        rc = _StubRC()  # list OK, watch 410s instantly → relist loop
        inf = SharedInformer(rc, relist_backoff=0.01)
        inf.backoff.attempts = 7  # pretend we're deep in the ladder
        inf.start()
        try:
            assert wait_until(lambda: rc.lists >= 4, 10), \
                f"relist loop stalled at {rc.lists} rounds (decayed-cap " \
                f"retry bug)"
            assert inf.backoff.attempts <= 2, \
                "backoff ladder not collapsed by the successful list"
        finally:
            inf.stop()

    def test_watch_signal_fully_resets_ladder(self):
        """The full reset happens once the watch phase actually delivers
        a signal — a healthy round ends with a clean slate."""
        from kubernetes_tpu.client import SharedInformer

        def live_watch():
            w = mwatch.Watch(capacity=8)
            w.send(mwatch.Event(mwatch.BOOKMARK, {
                "metadata": {"resourceVersion": "7"}}))
            return w

        rc = _StubRC(watch_fn=live_watch)
        inf = SharedInformer(rc, relist_backoff=0.01)
        inf.backoff.attempts = 7
        inf.start()
        try:
            assert wait_until(lambda: inf.bookmarks_seen >= 1, 10)
            assert wait_until(lambda: inf.backoff.attempts == 0, 5), \
                "healthy watch signal did not reset the ladder"
        finally:
            inf.stop()

    def test_failing_list_still_escalates(self):
        from kubernetes_tpu.client import SharedInformer

        def boom():
            raise RuntimeError("list down")

        rc = _StubRC(list_fn=boom)
        inf = SharedInformer(rc, relist_backoff=0.01)
        inf.start()
        try:
            assert wait_until(lambda: rc.lists >= 3, 10)
            assert inf.backoff.attempts >= 2  # no reset without success
        finally:
            inf.stop()

    def test_refused_watch_resumes_under_the_ladder(self):
        """A server refusing every watch re-establishment (429/503 as
        terminal ERROR frames) is pushback: resumes must pace on the
        capped-exponential ladder, not the bare 0.05 s resume cadence —
        ~20 attempts/s against a saturated apiserver would be the
        informer amplifying the very overload that refused it."""
        from kubernetes_tpu.client import SharedInformer

        def refused():
            w = mwatch.Watch(capacity=4)
            w.terminate(mwatch.Event(mwatch.ERROR, {"code": 429}))
            return w

        rc = _StubRC(watch_fn=refused)
        inf = SharedInformer(rc, relist_backoff=0.2)
        inf.start()
        try:
            time.sleep(1.0)
            assert rc.watches <= 8, \
                f"{rc.watches} watch attempts in 1s — refused watches " \
                f"are not pacing on the backoff ladder"
            assert inf.backoff.attempts >= 2  # consecutive refusals escalate
        finally:
            inf.stop()

    def test_stop_join_is_bounded_mid_backoff(self):
        """Satellite 1: stop() during the relist backoff sleep returns
        promptly — the sleep is interruptible, never a blocking wait up
        to the cap."""
        from kubernetes_tpu.client import SharedInformer

        def boom():
            raise RuntimeError("list down")

        rc = _StubRC(list_fn=boom)
        inf = SharedInformer(rc, relist_backoff=20.0)  # cap 30 s
        inf.backoff.attempts = 4  # pretend we're deep in the ladder
        inf.start()
        assert wait_until(lambda: rc.lists >= 1, 5)
        time.sleep(0.1)  # let the thread enter the backoff wait
        t0 = time.monotonic()
        inf.stop()
        took = time.monotonic() - t0
        assert took < 2.0, f"stop() blocked {took:.1f}s in the relist sleep"
        assert not inf._thread.is_alive()


class TestLastSync:
    """ISSUE 37: every list+replace round leaves `SharedInformer.last_sync`
    and one observation a stage of `informer_sync_duration_seconds`."""

    @staticmethod
    def _observed(stage):
        from kubernetes_tpu.client.informers import INFORMER_SYNC_DURATION

        return INFORMER_SYNC_DURATION.count(resource="stubs", stage=stage)

    @pytest.mark.parametrize("below", [True, False])
    def test_the_initial_list_files_the_store_below_the_round(self, below):
        """Through `Client.local` the round's Trace is the request's: the
        in-process apiserver and the store file themselves below `list`.
        With `trace_below` off (a server whose telemetry is off) the three
        stages alone are timed, and a handler finds no Trace."""
        from kubernetes_tpu.client import SharedInformer
        from kubernetes_tpu.component import trace

        api, client = _mkapi()
        decoded = []
        try:
            for i in range(5):
                client.pods.create(v1pod(f"p{i}"))
            inf = SharedInformer(client.pods, namespace="default")
            inf.trace_below = below
            inf.add_handlers(
                on_add=lambda o: decoded.append(trace.current() is not None))
            assert inf.last_sync is None
            inf.start()
            assert inf.wait_for_sync(10)
            sync = inf.last_sync     # written before the waiter is let go
            inf.stop()
        finally:
            api.close()
        assert (sync["resource"], sync["items"], sync["synced"]) == (
            "pods", 5, True) and decoded == [below] * 5
        ch = sync["children"]
        kv = "list/apiserver.list/store.list/kv"
        assert list(ch) == ["list", "list/apiserver.list",
                            "list/apiserver.list/store.list", kv, "index",
                            "handlers"] if below else ["list", "index",
                                                       "handlers"]
        if below:
            assert ch[kv][1] <= ch["list/apiserver.list/store.list"][1] \
                <= ch["list/apiserver.list"][1] <= ch["list"][1]
        stages = sum(ch[s][1] for s in ("list", "index", "handlers"))
        assert stages <= sync["duration_s"] <= stages + 0.05

    def test_a_relist_leaves_the_same(self):
        from kubernetes_tpu.client import SharedInformer
        from kubernetes_tpu.component import trace

        seen = []
        rc = _StubRC(list_fn=lambda: {     # list OK, the watch 410s: relist
            "items": [{"metadata": {"name": "a", "namespace": "d"}}],
            "metadata": {"resourceVersion": str(len(seen) + 1)}})
        n0 = self._observed("handlers")
        inf = SharedInformer(rc, relist_backoff=0.01)
        # what a handler files on `trace.current()` is the round's; the
        # second round delivers the known object as an update
        inf.add_handlers(
            on_add=lambda o: (seen.append("add"),
                              trace.current().child("decode", 0.25)),
            on_update=lambda old, new: (
                seen.append("update"), trace.current().child("decode", 0.5)))
        inf.start()
        try:
            assert inf.wait_for_sync(10)
            first = inf.last_sync
            assert wait_until(lambda: inf.relists >= 2 and inf.last_sync
                              is not first and inf.last_sync["synced"], 10)
            again = inf.last_sync
        finally:
            inf.stop()
        assert seen[:2] == ["add", "update"]
        assert first["children"]["handlers/decode"] == [1, 0.25, 0.25]
        assert again["children"]["handlers/decode"] == [1, 0.5, 0.5]
        assert again["t_start"] > first["t_start"]
        assert set(again) == set(first) == {
            "resource", "t_start", "duration_s", "items", "synced",
            "children"}
        assert self._observed("handlers") >= n0 + 2

    def test_a_round_that_does_not_end_is_not_synced(self):
        from kubernetes_tpu.client import SharedInformer

        def boom():
            raise RuntimeError("list down")

        rc = _StubRC(list_fn=boom)
        n0 = self._observed("list"), self._observed("handlers")
        inf = SharedInformer(rc, relist_backoff=0.01)
        inf.start()
        try:
            assert not inf.wait_for_sync(0.3)   # the verdict start() reads
            assert wait_until(lambda: inf.last_sync is not None, 5)
            sync = inf.last_sync
        finally:
            inf.stop()
        assert sync["synced"] is False and sync["items"] == 0
        assert sync["children"] == {}           # no stage ran to its end
        assert (self._observed("list"), self._observed("handlers")) == n0


# --------------------------------------------------------------------- #
# WatchMux: routing, backpressure, resync, death
# --------------------------------------------------------------------- #


class TestWatchMux:
    def _mux(self, api, client, **kw):
        from kubernetes_tpu.client import SharedInformer, WatchMux

        inf = SharedInformer(client.pods, namespace="default")
        return WatchMux(inf, **kw)

    def test_one_upstream_many_routes(self):
        api, client = _mkapi()
        mux = self._mux(api, client, buffer=256)
        got = {f"t{k}": [] for k in range(4)}
        for n in got:
            mux.route(n, on_add=lambda o, n=n: got[n].append(
                o["metadata"]["name"]))
        mux.start()
        try:
            assert mux.wait_for_sync(10)
            for i in range(40):
                client.pods.create(v1pod(f"p{i}", tenant=f"t{i % 4}"))
            assert wait_until(
                lambda: sum(len(v) for v in got.values()) == 40, 10)
            assert all(len(v) == 10 for v in got.values())
            # the acceptance number: 4 tenants, ONE apiserver stream
            assert api.storage.live_watchers("/registry/core/pods/") == 1
        finally:
            mux.stop()
            api.close()

    def test_late_route_synthesizes_from_indexer(self):
        api, client = _mkapi()
        mux = self._mux(api, client)
        mux.start()
        try:
            assert mux.wait_for_sync(10)
            client.pods.create(v1pod("early-bird", tenant="late"))
            assert wait_until(lambda: len(mux.informer.indexer) == 1, 10)
            relists = mux.informer.relists
            late = []
            r = mux.route("late", on_add=lambda o: late.append(
                o["metadata"]["name"]))
            assert wait_until(lambda: late == ["early-bird"], 5), late
            assert r.resyncs >= 1
            assert mux.informer.relists == relists, \
                "late-join resync must come from the indexer, not a relist"
        finally:
            mux.stop()
            api.close()

    def test_unrouted_events_counted_not_crashing(self):
        api, client = _mkapi()
        mux = self._mux(api, client)
        mux.route("t0")
        mux.start()
        try:
            assert mux.wait_for_sync(10)
            client.pods.create(v1pod("unlabeled"))
            assert wait_until(lambda: mux.unrouted_events >= 1, 5)
        finally:
            mux.stop()
            api.close()

    def test_tenant_label_move_is_delete_plus_add(self):
        api, client = _mkapi()
        mux = self._mux(api, client)
        a_events, b_events = [], []
        mux.route("a", on_add=lambda o: a_events.append(("add",)),
                  on_delete=lambda o: a_events.append(("del",)))
        mux.route("b", on_add=lambda o: b_events.append(("add",)))
        mux.start()
        try:
            assert mux.wait_for_sync(10)
            obj = client.pods.create(v1pod("mover", tenant="a"))
            assert wait_until(lambda: ("add",) in a_events, 5)
            obj["metadata"]["labels"]["ktpu.io/tenant"] = "b"
            client.pods.update(obj)
            assert wait_until(lambda: ("del",) in a_events, 5)
            assert wait_until(lambda: ("add",) in b_events, 5)
        finally:
            mux.stop()
            api.close()

    def test_slow_route_resyncs_from_indexer_not_apiserver(self):
        api, client = _mkapi()
        mux = self._mux(api, client, buffer=4)  # tiny route queues
        stall = threading.Event()
        seen = {}

        def on_add(o):
            if not stall.is_set():
                time.sleep(0.2)  # the slow consumer
            seen[o["metadata"]["name"]] = True

        mux.route("t0", on_add=on_add,
                  on_update=lambda o, n: seen.__setitem__(
                      n["metadata"]["name"], True))
        mux.start()
        try:
            assert mux.wait_for_sync(10)
            for i in range(30):
                client.pods.create(v1pod(f"s{i}", tenant="t0"))
            r = mux.routes["t0"]
            assert wait_until(lambda: r.evictions >= 1, 10), \
                "slow route never hit backpressure"
            stall.set()  # consumer recovers; resync converges the view
            assert wait_until(lambda: len(r.view) == 30, 15), \
                f"route never converged: {len(r.view)}/30"
            assert r.resyncs >= 1
            assert mux.informer.relists == 1, \
                "a route-local stall must never relist the apiserver"
            assert api.storage.live_watchers("/registry/core/pods/") == 1
        finally:
            mux.stop()
            api.close()

    def test_watch_stall_seam_breaks_one_route(self):
        api, client = _mkapi()
        faultline.install("watch.stall@t1:1")
        mux = self._mux(api, client)
        got = {"t0": [], "t1": []}
        for n in got:
            mux.route(n, on_add=lambda o, n=n: got[n].append(1))
        mux.start()
        try:
            assert mux.wait_for_sync(10)
            for i in range(10):
                client.pods.create(v1pod(f"w{i}", tenant=f"t{i % 2}"))
            assert wait_until(
                lambda: len(mux.routes["t1"].view) == 5
                and len(got["t0"]) == 5, 10)
            assert mux.routes["t1"].evictions >= 1
            assert mux.routes["t0"].evictions == 0  # isolation
        finally:
            mux.stop()
            api.close()

    def test_sequence_fence_discards_stale_inflight(self):
        from kubernetes_tpu.client import WatchMux  # noqa: F401
        from kubernetes_tpu.client.watchmux import MuxRoute

        applied = []
        r = MuxRoute("t", on_add=lambda o: applied.append(o), capacity=8)
        try:
            # an event stamped at-or-below the fence (a racer from before a
            # break) must be discarded, not applied
            with r._cv:
                r.fence = r.seq = 5
                r._q.append((5, "ADDED", None,
                             {"metadata": {"name": "stale"}}))
                r._cv.notify()
            assert wait_until(lambda: r.discarded_stale == 1, 5)
            assert not applied and not r.view
            r.offer("ADDED", None, {"metadata": {"name": "fresh"}})
            assert wait_until(lambda: len(applied) == 1, 5)
        finally:
            r.stop()

    def test_handler_errors_counted_not_fatal(self):
        from kubernetes_tpu.client.watchmux import MuxRoute

        applied = []

        def bad_add(o):
            raise RuntimeError("tenant handler bug")

        r = MuxRoute("t", on_add=bad_add, capacity=8)
        try:
            r.offer("ADDED", None, {"metadata": {"name": "x"}})
            assert wait_until(lambda: r.handler_errors == 1, 5)
            # the route thread survived: a later good event still flows
            r.on_add = lambda o: applied.append(o)
            r.offer("ADDED", None, {"metadata": {"name": "y"}})
            assert wait_until(lambda: len(applied) == 1, 5)
        finally:
            r.stop()

    def test_mux_die_seam_then_revive_resumes(self):
        api, client = _mkapi()
        faultline.install("mux.die@stream:3")
        mux = self._mux(api, client)
        got = []
        mux.route("t0", on_add=lambda o: got.append(o["metadata"]["name"]))
        mux.start()
        try:
            assert mux.wait_for_sync(10)
            for i in range(3):
                client.pods.create(v1pod(f"d{i}", tenant="t0"))
            assert wait_until(lambda: not mux.alive, 10), \
                "mux.die@stream never killed the stream"
            assert mux.deaths == 1
            relists = mux.informer.relists
            client.pods.create(v1pod("while-dead", tenant="t0"))
            faultline.uninstall()  # the drill is over; revive cleanly
            mux.revive()
            assert wait_until(lambda: "while-dead" in
                              [k.split("/")[-1] for k in
                               mux.routes["t0"].view], 10)
            assert mux.informer.relists == relists, \
                "revive must resume, not relist"
            assert mux.informer.resumes >= 1
        finally:
            mux.stop()
            api.close()


# --------------------------------------------------------------------- #
# the fleet plane: K tenants, 2 streams, staleness, storm drills
# --------------------------------------------------------------------- #


def _small_fleet(api, client, tenants=3, clk=None):
    from kubernetes_tpu.fleet import FleetServer
    from kubernetes_tpu.sched.scheduler import RecordingBinder
    from kubernetes_tpu.state.dims import Dims

    clk = clk or {"t": 0.0}
    srv = FleetServer(batch_size=16, base_dims=Dims(N=16, P=16, E=64),
                      clock=lambda: clk["t"])
    binders = {}
    for k in range(tenants):
        binders[f"t{k}"] = RecordingBinder()
        srv.add_tenant(f"t{k}", binder=binders[f"t{k}"])
    return srv, binders, clk


class TestFleetWatchPlane:
    def test_double_attach_raises(self):
        api, client = _mkapi()
        srv, binders, clk = _small_fleet(api, client, tenants=1)
        plane = srv.attach_watch_plane(client)
        try:
            with pytest.raises(ValueError):
                srv.attach_watch_plane(client)
        finally:
            plane.stop()
            api.close()

    def test_k_tenants_two_streams_total(self):
        api, client = _mkapi()
        srv, binders, clk = _small_fleet(api, client, tenants=6)
        plane = srv.attach_watch_plane(client)
        try:
            for k in range(6):
                client.nodes.create(v1node(f"t{k}-n0", tenant=f"t{k}"))
                client.pods.create(v1pod(f"t{k}-p0", tenant=f"t{k}"))
            assert wait_until(
                lambda: all(t.sched.queue.lengths()[0] == 1
                            for t in srv.tenants.values()), 15)
            # 6 tenants, 2 streams on the apiserver — not 12
            assert api.storage.live_watchers("/registry/core/pods/") == 1
            assert api.storage.live_watchers("/registry/core/nodes/") == 1
            assert plane.stats()["upstream_watches_per_resource"] == 1
        finally:
            plane.stop()
            api.close()

    @pytest.mark.chaos
    def test_mux_death_degrades_to_cached_state_and_recovers(self):
        """The ISSUE 13 acceptance drill in miniature: storm in pods, kill
        the pod mux mid-flight, keep ticking (served from cached state,
        staleness visible), revive via maintain(), lose nothing, bind
        everything exactly once."""
        api, client = _mkapi()
        srv, binders, clk = _small_fleet(api, client, tenants=2)
        plane = srv.attach_watch_plane(client)
        try:
            for k in range(2):
                client.nodes.create(v1node(f"t{k}-n0", tenant=f"t{k}"))
            for i in range(6):
                for k in range(2):
                    client.pods.create(v1pod(f"t{k}-p{i}", tenant=f"t{k}"))
            assert wait_until(
                lambda: all(t.sched.queue.lengths()[0] == 6
                            for t in srv.tenants.values()), 15)
            plane.pod_mux.die()
            time.sleep(1.0)
            # pods created while the stream is dead arrive after revive
            for k in range(2):
                client.pods.create(v1pod(f"t{k}-late", tenant=f"t{k}"))
            tk = srv.tick()  # maintain(): records staleness, revives
            clk["t"] += 1.0
            assert tk.staleness_seconds > 0.5
            assert plane.mux_failovers >= 1
            assert plane.pod_mux.informer.relists == 1, "revive relisted"
            assert wait_until(
                lambda: all(t.sched.queue.lengths()[0] +
                            len(binders[t.name].bound) >= 7
                            for t in srv.tenants.values()), 15), \
                "late pods never arrived post-revive"
            for _ in range(12):
                srv.tick()
                clk["t"] += 1.0
                if all(len(binders[f"t{k}"].bound) == 7 for k in range(2)):
                    break
            for k in range(2):
                keys = [key for key, _ in binders[f"t{k}"].bound]
                assert len(keys) == 7, f"t{k} lost pods: {len(keys)}/7"
                assert len(set(keys)) == 7, f"t{k} double-bound"
            # staleness decays back once the stream is live again
            assert plane.staleness() < 15.0
        finally:
            plane.stop()
            api.close()

    @pytest.mark.chaos
    def test_compaction_storm_relists_O1_not_OK(self):
        """Satellite 3: K tenants riding one mux through repeated
        compactions. Live streams ride the boundary bookmarks (zero
        relists); killing + reviving both muxes mid-storm resumes from
        bookmarked RVs (still zero); only a genuine floor-crossing while
        the stream is DOWN costs a relist — exactly ONE, not one per
        tenant. The ladder's jitter keeps even those from lockstep."""
        from kubernetes_tpu.storage.cacher import WatchCache

        api, client = _mkapi()
        K = 8
        srv, binders, clk = _small_fleet(api, client, tenants=K)
        plane = srv.attach_watch_plane(client)
        try:
            st = api.storage
            base_relists = sum(m.informer.relists for m in plane.muxes)
            assert base_relists == 2  # one initial sync per resource
            # ---- repeated compaction storm against LIVE streams ---- #
            for round_ in range(4):
                for k in range(K):
                    client.pods.create(
                        v1pod(f"r{round_}-t{k}", tenant=f"t{k}"))
                assert wait_until(
                    lambda: st._dispatched_rev >= st.kv.rev(), 5)
                st.compact_to(st.kv.rev())
            assert wait_until(
                lambda: all(len(m.informer.indexer) > 0
                            for m in (plane.pod_mux,)), 10)
            assert sum(m.informer.relists for m in plane.muxes) == 2, \
                "a compaction under a LIVE bookmarked stream must not relist"
            # ---- mux-kill mid-storm: resume from bookmarked RVs ---- #
            plane.pod_mux.die()
            plane.node_mux.die()
            st.compact_to(st.kv.rev())  # floor moves while they're dead...
            srv.tick()  # maintain revives both
            clk["t"] += 1.0
            assert plane.mux_failovers >= 2
            assert sum(m.informer.relists for m in plane.muxes) == 2, \
                "post-kill resume should ride the bookmarked RV (within " \
                "the cacher window), not relist"
            # a resume is only COUNTED once the re-established stream
            # delivers its first signal (an attempt that never delivers
            # resumed nothing) — nudge the pod stream and wait for it
            client.pods.create(v1pod("post-revive", tenant="t0"))
            assert wait_until(
                lambda: sum(m.informer.bookmark_resumes
                            for m in plane.muxes) >= 1, 10), \
                "no bookmark-funded resume in the drill"
            # ---- a GENUINE floor-crossing (cache gap) while down ---- #
            plane.pod_mux.die()
            client.pods.create(v1pod("gap", tenant="t0"))
            # let the pump dispatch past the write BEFORE compacting: the
            # drill targets the DEAD stream's stale token, not the pump's
            # own fell-behind-compaction path (which rightly 410s everyone)
            assert wait_until(lambda: st._dispatched_rev >= st.kv.rev(), 5)
            st.compact_to(st.kv.rev())
            st.watch_cache = WatchCache(horizon=st.kv.rev())
            srv.tick()
            clk["t"] += 1.0
            assert wait_until(
                lambda: any("gap" in key for key in
                            plane.pod_mux.routes["t0"].view), 15)
            relists = sum(m.informer.relists for m in plane.muxes)
            assert relists == 3, \
                f"one floor-crossing must cost ONE relist (got " \
                f"{relists - 2}) — O(1), not O(K={K})"
            # no-lockstep: the relist ladder is jittered by construction
            from kubernetes_tpu.client.informers import RelistBackoff

            delays = {RelistBackoff(base=0.5).next() for _ in range(16)}
            assert len(delays) > 1, "relist delays are lockstep-identical"
        finally:
            plane.stop()
            api.close()

    def test_staleness_metric_exported_per_tenant(self):
        from kubernetes_tpu.component.metrics import DEFAULT_REGISTRY

        api, client = _mkapi()
        srv, binders, clk = _small_fleet(api, client, tenants=2)
        plane = srv.attach_watch_plane(client)
        try:
            srv.tick()
            text = DEFAULT_REGISTRY.expose_text()
            for k in range(2):
                assert f'tenant_staleness_seconds{{tenant="t{k}"}}' in text
        finally:
            plane.stop()
            api.close()

    def test_buffer_depth_metric_exported(self):
        from kubernetes_tpu.storage.store import WATCH_BUFFER_DEPTH

        st = Storage(kv=PyKV())
        try:
            w = st.watch("/registry/core/pods/")
            st.create("/registry/core/pods/default/a",
                      {"metadata": {"name": "a"}})
            assert wait_until(
                lambda: st._dispatched_rev >= st.kv.rev(), 5)
            # the gauge exists and carries the pods resource label
            assert WATCH_BUFFER_DEPTH.value(resource="pods") >= 0
            w.stop()
        finally:
            st.close()


# --------------------------------------------------------------------- #
# one long wave through the watch plane (ISSUE 26): the density deployment
# at its rehearsal size, the store's buffers smaller than the wave
# --------------------------------------------------------------------- #


class TestOneLongWaveThroughTheWatchPlane:
    def test_wave_larger_than_the_buffers_costs_no_relist(self):
        """800 plain pods on 64 nodes bind in ONE wave whose Bindings
        outnumber every watcher's buffer six times over. The scheduler's
        own informer is blocked behind the wave for all of it and a
        client's watch drains as it can: neither relists, neither is cut
        off, every pod is bound once, validly, and the wave's record says
        what the watch plane did."""
        from kubernetes_tpu.api import semantics as sem
        from kubernetes_tpu.api.types import Resources
        from kubernetes_tpu.api.v1 import node_from_v1, pod_from_v1
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.client import Client
        from kubernetes_tpu.sched.ledger import BindIntentLedger
        from kubernetes_tpu.sched.scheduler import Scheduler
        from kubernetes_tpu.sched.server import APIBinder, SchedulerServer
        from kubernetes_tpu.state.dims import Dims

        n_nodes, n_pods, buffer = 64, 800, 128
        tiers = [("100m", "128Mi"), ("250m", "512Mi"), ("500m", "1Gi"),
                 ("1", "2Gi")]
        api = APIServer(watch_buffer=buffer)
        client = Client.local(api)
        srv = None
        try:
            for i in range(n_nodes):
                client.nodes.create({
                    "apiVersion": "v1", "kind": "Node",
                    "metadata": {"name": f"node-{i}"},
                    "status": {"allocatable": {
                        "cpu": "32", "memory": "128Gi", "pods": "110"}}})
            for i in range(n_pods):
                cpu, mem = tiers[i % 4]
                client.pods.create({
                    "apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": f"job-{i}", "namespace": "default"},
                    "spec": {"containers": [{
                        "name": "c", "image": "i", "resources": {
                            "requests": {"cpu": cpu, "memory": mem}}}]}})
            # a client's own watch, as the benchmark's: a stream the store
            # cuts off would end it, and a relist would be the only way on
            listing = client.pods.list("default")
            cw = client.pods.watch(
                "default",
                resource_version=listing["metadata"]["resourceVersion"])
            seen, stream_errors = [], []

            def consume():
                for ev in cw:
                    if ev.type == mwatch.ERROR:
                        stream_errors.append(ev.object)
                    elif ev.object.get("spec", {}).get("nodeName"):
                        seen.append((ev.object["metadata"]["name"],
                                     ev.object["spec"]["nodeName"]))

            t = threading.Thread(target=consume, daemon=True)
            t.start()
            dims = Dims(N=64, D=64, P=1024, SC=64, SL=64).grown_for(E=4096)
            sched = Scheduler(binder=APIBinder(client), batch_size=dims.P,
                              base_dims=dims)
            srv = SchedulerServer(
                client, scheduler=sched, cycle_interval=0.02,
                batch_window=0.15,
                ledger=BindIntentLedger(api.storage, identity="t"))
            srv.start()
            relists0 = srv.pod_informer.relists + srv.node_informer.relists
            assert wait_until(lambda: len(seen) >= n_pods, 120, 0.05), \
                f"{len(seen)} of {n_pods} Bindings reached the client"
            # the informer's confirmations: every assume confirmed
            assert wait_until(
                lambda: sched.cache.drain_confirm_waits()[1] == 0, 30, 0.05)
            waves = [r for r in sched.telemetry.recorder.records()
                     if (r.get("stats") or {}).get("attempted")]
            assert [w["stats"]["scheduled"] for w in waves] == [n_pods], \
                "the backlog was to drain in ONE wave"
            rec = waves[0]
            assert rec["informer_relists"] == 0
            assert rec["watch_evictions"] == 0
            assert rec["pump_lag_max"] >= 0
            # what the broadcast cost while the wave ran (ISSUE 28): the
            # wave's Bindings were broadcast beside it (the last few may
            # follow its end), in fewer turns than events
            assert rec["pump_events"] >= n_pods // 2
            assert 0 < rec["pump_turns"] < rec["pump_events"]
            assert rec["pump_busy_s"] > 0
            # across the wave and the catch-up after it: nobody relisted,
            # nobody was cut off, and the client's stream never ended
            assert srv.pod_informer.relists + srv.node_informer.relists \
                == relists0
            assert api.storage.deaf_evictions == 0
            assert not stream_errors and not cw.stopped
            assert srv.pod_informer.resumes == 0
            # every pod bound once, where the apiserver says, validly
            assert len(seen) == n_pods == len({n for n, _ in seen})
            pods = client.pods.list("default")["items"]
            assert {(p["metadata"]["name"], p["spec"].get("nodeName"))
                    for p in pods} == set(seen)
            nodes = {n["metadata"]["name"]: node_from_v1(n)
                     for n in client.nodes.list()["items"]}
            used = {n: (0, 0, 0) for n in nodes}
            for obj in pods:
                pod = pod_from_v1(obj)
                cpu, mem, count = used[pod.node_name]
                ok, why = sem.pod_fits_resources(
                    pod, nodes[pod.node_name],
                    Resources(milli_cpu=cpu, memory_kib=mem), count)
                assert ok, (pod.key, why)
                used[pod.node_name] = (cpu + pod.requests.milli_cpu,
                                       mem + pod.requests.memory_kib,
                                       count + 1)
            assert sched.ledger.unretired() == []
            cw.stop()
            t.join(timeout=3)
        finally:
            if srv is not None:
                srv.stop()
            api.close()
