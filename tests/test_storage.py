"""kvstore + storage.Interface: CRUD, CAS, watch, compaction.

Both backends (native C++ and the Python replica) run the same tables,
mirroring how the reference tests etcd3 storage against a real etcd
(storage/etcd3/store_test.go).
"""

import threading
import time

import pytest

from kubernetes_tpu.machinery import errors
from kubernetes_tpu.machinery import watch as mwatch
from kubernetes_tpu.storage import native
from kubernetes_tpu.storage.store import Storage


@pytest.fixture(params=["native", "python"])
def kv(request):
    if request.param == "native":
        try:
            store = native.NativeKV()
        except RuntimeError:
            pytest.skip("native kvstore not buildable here")
    else:
        store = native.PyKV()
    yield store
    store.close()


class TestKV:
    def test_put_get_rev(self, kv):
        r1 = kv.put("/a", b"1")
        r2 = kv.put("/a", b"2")
        assert r2 == r1 + 1
        rec = kv.get("/a")
        assert rec.value == b"2" and rec.create_rev == r1 and rec.mod_rev == r2
        assert kv.get("/missing") is None
        assert kv.rev() == r2

    def test_txn_semantics(self, kv):
        assert kv.txn_put("/x", 0, b"v1") > 0          # create
        assert kv.txn_put("/x", 0, b"v2") == -1        # create-only fails
        mod = kv.get("/x").mod_rev
        assert kv.txn_put("/x", mod, b"v2") > 0        # CAS ok
        assert kv.txn_put("/x", mod, b"v3") == -1      # stale CAS fails
        assert kv.txn_delete("/x", mod) == -1          # stale delete fails
        assert kv.txn_delete("/x", kv.get("/x").mod_rev) > 0
        assert kv.txn_delete("/x") == 0                # already gone

    def test_range_and_count(self, kv):
        for i in range(5):
            kv.put(f"/pods/ns1/p{i}", b"x")
        kv.put("/nodes/n1", b"y")
        recs, at_rev = kv.range("/pods/")
        assert [r.key for r in recs] == [f"/pods/ns1/p{i}" for i in range(5)]
        assert at_rev == kv.rev()
        assert kv.count("/pods/") == 5
        assert kv.count("/nodes/") == 1
        assert kv.range("/none/")[0] == []

    def test_events_and_compaction(self, kv):
        r0 = kv.rev()
        kv.put("/a", b"1")
        kv.put("/b", b"2")
        kv.txn_delete("/a")
        evs = kv.events_since(r0)
        assert [(e.type, e.key) for e in evs] == [
            (native.EVENT_CREATE, "/a"), (native.EVENT_CREATE, "/b"),
            (native.EVENT_DELETE, "/a")]
        assert evs[2].value == b"1"  # delete carries prev value
        # create → update distinction
        kv.put("/b", b"3")
        evs2 = kv.events_since(evs[-1].rev)
        assert evs2[0].type == native.EVENT_PUT
        # compaction
        cut = evs[1].rev
        kv.compact(cut)
        with pytest.raises(native.CompactedError):
            kv.events_since(r0)
        assert [e.key for e in kv.events_since(cut)] == ["/a", "/b"]

    def test_wait_blocks_until_write(self, kv):
        r = kv.rev()
        t0 = time.monotonic()
        threading.Timer(0.15, lambda: kv.put("/w", b"1")).start()
        new_rev = kv.wait(r, timeout=5)
        assert new_rev > r
        assert 0.05 < time.monotonic() - t0 < 3

    def test_wait_timeout(self, kv):
        r = kv.rev()
        assert kv.wait(r, timeout=0.05) == r


@pytest.fixture(params=["native", "python"])
def storage(request):
    if request.param == "native":
        try:
            backend = native.NativeKV()
        except RuntimeError:
            pytest.skip("native kvstore not buildable here")
    else:
        backend = native.PyKV()
    s = Storage(kv=backend)
    yield s
    s.close()


def _pod(name, ns="default", **spec):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": ns}, "spec": spec}


# what the tree at PR 26 stored for the pod of the golden test below
_GOLDEN_CREATED = (
    b'{"apiVersion":"v1","kind":"Pod","metadata":{"creationTimestamp":"2'
    b'026-01-01T00:00:00Z","generation":1,"labels":{"app":"app-1"},"name'
    b'":"golden","namespace":"default","uid":"00000000-0000-4000-8000-00'
    b'0000000028"},"spec":{"affinity":{"podAntiAffinity":{"requiredDurin'
    b'gSchedulingIgnoredDuringExecution":[{"labelSelector":{"matchExpres'
    b'sions":[{"key":"app","operator":"In","values":["app-1"]}]},"topolo'
    b'gyKey":"kubernetes.io/hostname"}]}},"containers":[{"image":"regist'
    b'ry/app:v1","imagePullPolicy":"IfNotPresent","name":"main","ports":'
    b'[],"resources":{"requests":{"cpu":"100m","memory":"500Mi"}},"termi'
    b'nationMessagePath":"/dev/termination-log"}],"dnsPolicy":"ClusterFi'
    b'rst","enableServiceLinks":true,"priority":1,"restartPolicy":"Alway'
    b's","schedulerName":"default-scheduler","serviceAccountName":"defau'
    b'lt","terminationGracePeriodSeconds":30,"tolerations":[{"effect":"N'
    b'oExecute","key":"node.kubernetes.io/not-ready","operator":"Exists"'
    b',"tolerationSeconds":300},{"effect":"NoExecute","key":"node.kubern'
    b'etes.io/unreachable","operator":"Exists","tolerationSeconds":300}]'
    b'},"status":{"phase":"Pending"}}')


class TestStorage:
    def test_create_get_conflict(self, storage):
        out = storage.create("/registry/pods/default/a", _pod("a"), "pods")
        assert out["metadata"]["resourceVersion"]
        got = storage.get("/registry/pods/default/a", "pods", "a")
        assert got["metadata"]["name"] == "a"
        assert got["metadata"]["resourceVersion"] == out["metadata"]["resourceVersion"]
        with pytest.raises(errors.StatusError) as ei:
            storage.create("/registry/pods/default/a", _pod("a"), "pods")
        assert errors.is_already_exists(ei.value)

    def test_guaranteed_update_cas_and_conflict(self, storage):
        storage.create("/registry/pods/default/a", _pod("a"), "pods")
        got = storage.get("/registry/pods/default/a")
        rv = got["metadata"]["resourceVersion"]

        def set_node(obj):
            obj["spec"]["nodeName"] = "n1"
            return obj

        updated = storage.guaranteed_update("/registry/pods/default/a",
                                            set_node, "pods", "a")
        assert updated["spec"]["nodeName"] == "n1"
        assert int(updated["metadata"]["resourceVersion"]) > int(rv)
        # stale precondition → Conflict
        with pytest.raises(errors.StatusError) as ei:
            storage.guaranteed_update("/registry/pods/default/a", set_node,
                                      "pods", "a", expected_rv=rv)
        assert errors.is_conflict(ei.value)

    def test_guaranteed_update_retries_on_race(self, storage):
        storage.create("/registry/x", {"metadata": {"name": "x"}, "n": 0})
        n_threads, per = 8, 25

        def bump(obj):
            obj["n"] += 1
            return obj

        def worker():
            for _ in range(per):
                storage.guaranteed_update("/registry/x", bump)

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert storage.get("/registry/x")["n"] == n_threads * per

    @pytest.mark.parametrize("via", ["create", "get", "list", "update",
                                     "update_fn_lost_cas", "delete_then_create"])
    def test_what_the_store_hands_out_is_the_callers_own(self, storage, via):
        """The store keeps bytes and copies nothing (ISSUE 28): changing what
        `create` / `get` / `list` / `guaranteed_update` returned, or what
        `update_fn` was given on an attempt that then loses its CAS,
        changes neither the stored bytes nor a later read."""
        import json

        key = "/registry/pods/default/own"
        made = storage.create(key, _pod("own", labels={"a": "1"},
                                        containers=[{"name": "c"}]), "pods")

        def scribble(obj):
            obj["spec"]["labels"]["a"] = "scribbled"
            obj["spec"]["containers"].append({"name": "intruder"})
            obj["metadata"]["name"] = "renamed"
            obj["junk"] = True

        def bind(obj):
            obj["spec"]["nodeName"] = "n1"
            return obj

        if via == "create":
            handed = made
        elif via == "get":
            handed = storage.get(key, "pods", "own")
        elif via == "list":
            (handed,), _ = storage.list("/registry/pods/default/")
        elif via == "update":
            handed = storage.guaranteed_update(key, bind, "pods", "own")
        elif via == "update_fn_lost_cas":
            given = []

            def racing(obj):
                given.append(json.loads(json.dumps(obj)))
                if len(given) == 1:
                    # this attempt changes what it was given, then loses
                    # its CAS to a writer that got in first
                    scribble(obj)
                    storage.guaranteed_update(
                        key, lambda o: {**o, "winner": True}, "pods", "own")
                    return obj
                return bind(obj)

            handed = storage.guaranteed_update(key, racing, "pods", "own")
            # the second attempt was given the winner's object, fresh:
            # nothing of what the first did to its own
            assert len(given) == 2 and given[1]["winner"] is True
            assert "junk" not in given[1]
            assert given[1]["metadata"]["name"] == "own"
            assert given[1]["spec"]["labels"] == {"a": "1"}
            assert handed["winner"] is True and "junk" not in handed
        else:
            # what delete returns is the caller's too, and so is the
            # object a create was given once it is returned
            gone = storage.delete(key, "pods", "own")
            scribble(gone)
            handed = storage.create(key, _pod("own", labels={"a": "1"},
                                              containers=[{"name": "c"}]),
                                    "pods")
        stored = storage.kv.get(key).value
        before = storage.get(key, "pods", "own")
        assert handed == before   # resourceVersion and all
        scribble(handed)
        assert storage.kv.get(key).value == stored
        assert storage.get(key, "pods", "own") == before
        (listed,), _ = storage.list("/registry/pods/default/")
        assert listed == before
        assert "junk" not in before and b"junk" not in stored

    def test_stored_bytes_of_a_create_and_a_binding_are_the_parents(
            self, monkeypatch):
        """Byte for byte what the tree before ISSUE 28 stored for the same
        create and the same Binding (what the WAL and the PyKV parity
        compare): key order, separators, no resourceVersion."""
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.client import Client
        from kubernetes_tpu.machinery import meta

        monkeypatch.setattr(meta, "new_uid",
                            lambda: "00000000-0000-4000-8000-000000000028")
        monkeypatch.setattr(meta, "now_rfc3339",
                            lambda: "2026-01-01T00:00:00Z")
        api = APIServer()
        try:
            client = Client.local(api)
            client.pods.create({
                "apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": "golden", "namespace": "default",
                             "labels": {"app": "app-1"}},
                "spec": {"schedulerName": "default-scheduler", "priority": 1,
                         "containers": [{
                             "name": "main", "image": "registry/app:v1",
                             "resources": {"requests": {"cpu": "100m",
                                                        "memory": "500Mi"}},
                             "ports": []}],
                         "affinity": {"podAntiAffinity": {
                             "requiredDuringSchedulingIgnoredDuringExecution":
                             [{"labelSelector": {"matchExpressions": [
                                 {"key": "app", "operator": "In",
                                  "values": ["app-1"]}]},
                               "topologyKey": "kubernetes.io/hostname"}]}}}})
            key = "/registry/core/pods/default/golden"
            created = _GOLDEN_CREATED
            assert api.storage.kv.get(key).value == created
            client.pods.bind("golden", "node-7", "default")
            bound = created.replace(
                b'"enableServiceLinks":true,',
                b'"enableServiceLinks":true,"nodeName":"node-7",').replace(
                b'"status":{"phase":"Pending"}',
                b'"status":{"conditions":[{"lastTransitionTime":'
                b'"2026-01-01T00:00:00Z","status":"True","type":'
                b'"PodScheduled"}],"phase":"Pending"}')
            assert api.storage.kv.get(key).value == bound
        finally:
            api.close()

    def test_delete(self, storage):
        storage.create("/registry/pods/default/a", _pod("a"), "pods")
        gone = storage.delete("/registry/pods/default/a", "pods", "a")
        assert gone["metadata"]["name"] == "a"
        with pytest.raises(errors.StatusError):
            storage.get("/registry/pods/default/a", "pods", "a")
        with pytest.raises(errors.StatusError):
            storage.delete("/registry/pods/default/a", "pods", "a")

    def test_list_with_predicate(self, storage):
        for i in range(4):
            storage.create(f"/registry/pods/default/p{i}", _pod(f"p{i}"), "pods")
        storage.create("/registry/pods/kube-system/s0", _pod("s0", "kube-system"), "pods")
        items, rv = storage.list("/registry/pods/default/")
        assert len(items) == 4 and int(rv) > 0
        odd, _ = storage.list("/registry/pods/",
                              lambda o: o["metadata"]["name"].endswith(("1", "3")))
        assert {o["metadata"]["name"] for o in odd} == {"p1", "p3"}

    def test_watch_live_and_catchup(self, storage):
        w = storage.watch("/registry/pods/")
        storage.create("/registry/pods/default/a", _pod("a"), "pods")
        storage.guaranteed_update("/registry/pods/default/a",
                                  lambda o: {**o, "spec": {"nodeName": "n1"}})
        storage.delete("/registry/pods/default/a")
        evs = [w.next(timeout=2) for _ in range(3)]
        assert [e.type for e in evs] == [mwatch.ADDED, mwatch.MODIFIED, mwatch.DELETED]
        assert evs[1].object["spec"]["nodeName"] == "n1"
        w.stop()

        # catch-up from an old rv replays history
        rv0 = evs[0].object["metadata"]["resourceVersion"]
        w2 = storage.watch("/registry/pods/", since_rv=rv0)
        evs2 = [w2.next(timeout=2) for _ in range(2)]
        assert [e.type for e in evs2] == [mwatch.MODIFIED, mwatch.DELETED]
        w2.stop()

    def test_watch_bookmarks_opt_in(self, monkeypatch):
        """WatchBookmarks (cacher.go bookmark timer): opted-in watchers get
        periodic BOOKMARK events carrying the dispatched revision; plain
        watchers never see them."""
        monkeypatch.setenv("KTPU_WATCH_BOOKMARK_INTERVAL", "0.3")
        storage = Storage(kv=native.new_kv(prefer_native=False))
        try:
            wb = storage.watch("/registry/pods/", bookmarks=True)
            plain = storage.watch("/registry/pods/")
            storage.create("/registry/pods/default/a", _pod("a"), "pods")
            seen = []
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                ev = wb.next(timeout=0.5)
                if ev is not None:
                    seen.append(ev)
                if any(e.type == mwatch.BOOKMARK for e in seen):
                    break
            bms = [e for e in seen if e.type == mwatch.BOOKMARK]
            assert bms, "no bookmark within 5s at a 0.3s interval"
            rv = int(bms[0].object["metadata"]["resourceVersion"])
            assert rv >= 1
            # the plain watcher got the ADDED event and nothing else
            ev = plain.next(timeout=2)
            assert ev.type == mwatch.ADDED
            assert plain.next(timeout=0.8) is None
            wb.stop()
            plain.stop()
        finally:
            storage.close()

    def test_watch_predicate_filters(self, storage):
        w = storage.watch("/registry/pods/",
                          predicate=lambda o: o["metadata"]["namespace"] == "prod")
        storage.create("/registry/pods/default/a", _pod("a"), "pods")
        storage.create("/registry/pods/prod/b", _pod("b", "prod"), "pods")
        ev = w.next(timeout=2)
        assert ev.object["metadata"]["name"] == "b"
        w.stop()

    @pytest.mark.parametrize("other", ["sibling", "resume", "predicate"])
    def test_what_a_stream_receives_is_its_own(self, storage, other):
        """Two streams on one prefix: changing the object one received
        leaves intact what a sibling stream receives, what a resume is
        replayed from the ring, and what a predicate is shown (ISSUE 28:
        one object per reader, none shared)."""
        import json

        shown = []

        def pred(obj):
            shown.append(json.loads(json.dumps(obj)))
            return True

        key = "/registry/pods/default/s"
        storage.create("/registry/nodes/n0", {"metadata": {"name": "n0"}})
        rv0 = str(storage.kv.rev())   # "0" would mean "from now"
        first = storage.watch("/registry/pods/")
        second = storage.watch(
            "/registry/pods/", predicate=pred if other == "predicate" else None)
        storage.create(key, _pod("s", labels={"a": "1"}), "pods")
        storage.guaranteed_update(
            key, lambda o: {**o, "spec": {**o["spec"], "nodeName": "n1"}})
        mine = [first.next(timeout=2) for _ in range(2)]
        want = [json.loads(json.dumps(e.object)) for e in mine]
        for e in mine:
            e.object["spec"]["labels"]["a"] = "scribbled"
            e.object["metadata"]["name"] = "renamed"
            e.object["junk"] = True
        if other == "resume":
            # a catch-up replayed from the cacher ring, after the scribble
            second.stop()
            second = storage.watch("/registry/pods/", since_rv=rv0)
        theirs = [second.next(timeout=2) for _ in range(2)]
        assert [e.type for e in theirs] == [mwatch.ADDED, mwatch.MODIFIED]
        assert [e.object for e in theirs] == want
        assert all(a.object is not b.object for a, b in zip(mine, theirs))
        if other == "predicate":
            assert shown == want
            # what a stream with a predicate received is its own as well:
            # a later stream's predicate is shown the event unchanged
            for e in theirs:
                e.object["junk"] = True
            del shown[:]
            third = storage.watch("/registry/pods/", since_rv=rv0,
                                  predicate=pred)
            assert [third.next(timeout=2).object for _ in range(2)] == want
            assert shown == want
            third.stop()
        first.stop()
        second.stop()

    def test_watch_gone_after_compaction(self, storage):
        from kubernetes_tpu.storage.cacher import WatchCache

        storage.create("/registry/pods/default/a", _pod("a"), "pods")
        storage.create("/registry/pods/default/b", _pod("b"), "pods")
        # let the pump ingest both events into the watch cache first, so the
        # compaction below cannot race it into the all-watchers-gone path
        deadline = time.time() + 2
        while storage._dispatched_rev < storage.kv.rev() \
                and time.time() < deadline:
            time.sleep(0.01)
        storage.kv.compact(storage.kv.rev())
        # since_rv == compaction point is still legal (needs only events > rv)
        w = storage.watch("/registry/pods/", since_rv=str(storage.kv.rev()))
        w.stop()
        # a resume WITHIN the watch-cache window is served from memory even
        # though the KV store compacted it away (cacher.go:369-374) — the
        # Cacher tier exists precisely to decouple watchers from compaction
        w2 = storage.watch("/registry/pods/", since_rv="1")
        ev = w2.next(timeout=2)
        assert ev is not None and ev.object["metadata"]["name"] == "b"
        w2.stop()
        # a resume below the CACHE horizon falls through to storage, which
        # compacted → 410 (the reflector relists)
        storage.watch_cache = WatchCache(horizon=storage.kv.rev())
        with pytest.raises(errors.StatusError) as ei:
            storage.watch("/registry/pods/", since_rv="1")
        assert errors.is_gone(ei.value)

    def test_pump_compaction_errors_watchers(self, storage):
        """A dispatcher that falls behind compaction must ERROR+stop live
        watchers (they need a relist), not skip silently."""
        w = storage.watch("/registry/pods/")
        # simulate the pump losing the race: compact beyond dispatched rev
        storage.create("/registry/pods/default/a", _pod("a"), "pods")
        ev = w.next(timeout=2)
        assert ev.type == mwatch.ADDED
        # force a gap: compact everything, then rewind the pump's cursor to a
        # compacted revision before the next event wakes it
        storage.kv.compact(storage.kv.rev())
        storage._dispatched_rev = 0
        storage.kv.put("/registry/pods/default/trigger", b"{}")
        end = w.next(timeout=3)
        assert end is not None and end.type == mwatch.ERROR
        assert w.next(timeout=0.5) is None  # stopped


class TestWatchCache:
    """Cacher tier (storage/cacher.py ⇔ cacher.go:309): N watchers must not
    multiply storage reads, and events are decoded once."""

    def test_catchup_reads_independent_of_watcher_count(self):
        from kubernetes_tpu.storage.store import Storage

        storage = Storage()
        try:
            for i in range(10):
                storage.create(f"/registry/pods/default/p{i}", _pod(f"p{i}"),
                               "pods")
            # let the pump populate the ring
            deadline = time.time() + 2
            while storage._dispatched_rev < storage.kv.rev() \
                    and time.time() < deadline:
                time.sleep(0.01)

            reads = []
            orig = storage.kv.events_since

            def counting(rev, prefix):
                reads.append(rev)
                return orig(rev, prefix)

            storage.kv.events_since = counting
            watchers = [storage.watch("/registry/pods/", since_rv="1")
                        for _ in range(32)]
            # every catch-up (revs 2..10, 9 events each) came from the ring:
            # the backing store saw ZERO reads for 32 watchers
            assert reads == [], f"storage reads on cached catch-up: {reads}"
            assert storage.watch_cache.hits >= 32
            for w in watchers:
                for _ in range(9):
                    ev = w.next(timeout=2)
                    assert ev is not None and ev.type == mwatch.ADDED
                w.stop()
        finally:
            storage.close()

    def test_prehorizon_resume_falls_back_once(self):
        from kubernetes_tpu.storage.cacher import WatchCache
        from kubernetes_tpu.storage.store import Storage

        storage = Storage()
        try:
            for i in range(4):
                storage.create(f"/registry/pods/default/p{i}", _pod(f"p{i}"),
                               "pods")
            # the resume is of a stream the pump had broadcast in full
            # (behind the pump there is nothing to catch up on yet)
            deadline = time.monotonic() + 5
            while storage.dispatched_rev < storage.kv.rev() \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            # shrink the window so rev 1 predates the horizon
            storage.watch_cache = WatchCache(horizon=storage.kv.rev())
            before = storage.watch_cache.storage_fallbacks
            w = storage.watch("/registry/pods/", since_rv="1")
            assert storage.watch_cache.storage_fallbacks == before + 1
            for _ in range(3):  # revs 2..4
                ev = w.next(timeout=2)
                assert ev is not None and ev.type == mwatch.ADDED
            w.stop()
        finally:
            storage.close()
