"""Durable control plane: WAL + snapshot persistence, crash recovery,
revision continuity, and the cold-restart drill (ISSUE 19).

The contract under test is etcd's: an acknowledged write is on disk before
it is visible; a committed-but-unacknowledged write MAY surface after
reboot; a reissued revision may NEVER happen — the revision counter resumes
from the last durable revision, so every watch resume token in the fleet
stays meaningful across process death. The recovery decision table:

    clean tail            replay everything
    torn final record     truncate, continue (the crash interrupted an
                          unacknowledged append)
    mid-log corruption    refuse to start (WalCorruptionError)
    corrupt snapshot      refuse to start

Both KV backends share one WAL format (byte-identical logs — the parity
goldens), so the dlopen-fallback path can crash on one backend and recover
on the other.
"""

import os
import time
import zlib

import pytest

from kubernetes_tpu.storage import native, wal
from kubernetes_tpu.storage.native import DurableKV, NativeKV, PyKV
from kubernetes_tpu.storage.store import Storage
from kubernetes_tpu.utils import faultline

pytestmark = pytest.mark.durability


@pytest.fixture(autouse=True)
def _clean_faultline():
    yield
    faultline.uninstall()


def _mk_backend(param):
    if param == "native":
        try:
            return NativeKV()
        except RuntimeError:
            pytest.skip("native kvstore not buildable here")
    return PyKV()


@pytest.fixture(params=["native", "python"])
def backend_kind(request):
    if request.param == "native":
        _mk_backend("native")  # skip early if unbuildable
    return request.param


def _durable(tmp_path, kind="python", durability="always", **kw):
    return DurableKV(_mk_backend(kind), str(tmp_path / "store"),
                     durability=durability, **kw)


def _wal_bytes(data_dir):
    """Every segment's bytes, in sequence order (the parity golden)."""
    return b"".join(open(p, "rb").read()
                    for _, p in wal.list_segments(data_dir))


# --------------------------------------------------------------------- #
# framing
# --------------------------------------------------------------------- #


class TestFraming:
    def test_record_roundtrip(self):
        for op, rev, key, val in [
                (wal.OP_PUT, 1, "/registry/pods/ns/p0", b"\x00payload\xff"),
                (wal.OP_DELETE, 9, "/registry/nodes/né", b""),
                (wal.OP_COMPACT, 12345, "", b"")]:
            rec = wal.decode_record(wal.encode_record(op, rev, key, val))
            assert (rec.op, rec.rev, rec.key, rec.value) == (op, rev, key,
                                                             val)

    def test_frame_carries_crc_of_payload(self):
        payload = wal.encode_record(wal.OP_PUT, 7, "/k", b"v")
        framed = wal.frame(payload)
        assert framed[8:] == payload
        import struct

        length, crc = struct.unpack("<II", framed[:8])
        assert length == len(payload)
        assert crc == zlib.crc32(payload)

    def test_garbage_payload_refused(self):
        with pytest.raises(wal.WalCorruptionError):
            wal.decode_record(b"\x99" + b"\x00" * 20)


# --------------------------------------------------------------------- #
# persistence + revision continuity (both backends, one WAL format)
# --------------------------------------------------------------------- #


class TestPersistence:
    def test_full_state_survives_restart(self, tmp_path, backend_kind):
        kv = _durable(tmp_path, backend_kind)
        r1 = kv.put("/registry/pods/a", b"v1")
        r2 = kv.txn_put("/registry/pods/b", 0, b"v2")
        r3 = kv.txn_put("/registry/pods/a", r1, b"v1b")
        r4 = kv.txn_delete("/registry/pods/b")
        assert (r1, r2, r3, r4) == (1, 2, 3, 4)
        kv.close()

        kv2 = _durable(tmp_path, backend_kind)
        assert kv2.recovered
        assert kv2.rev() == 4
        rec = kv2.get("/registry/pods/a")
        assert (rec.value, rec.create_rev, rec.mod_rev) == (b"v1b", 1, 3)
        assert kv2.get("/registry/pods/b") is None
        # RV continuity: the next write continues the pre-crash sequence
        assert kv2.put("/registry/pods/c", b"v5") == 5
        kv2.close()

    def test_cas_semantics_enforced_by_wrapper(self, tmp_path):
        kv = _durable(tmp_path)
        assert kv.txn_put("/x", 0, b"v1") == 1
        assert kv.txn_put("/x", 0, b"v2") == -1     # create-only fails
        assert kv.txn_put("/x", 99, b"v2") == -1    # stale CAS fails
        assert kv.txn_delete("/x", 99) == -1
        assert kv.txn_delete("/missing") == 0
        # refused mutations must leave NOTHING in the log: only the one
        # successful create replays
        kv.close()
        kv2 = _durable(tmp_path)
        assert kv2.rev() == 1
        assert kv2.get("/x").value == b"v1"
        kv2.close()

    def test_events_replayed_for_resume_above_floor(self, tmp_path):
        kv = _durable(tmp_path)
        for i in range(6):
            kv.put(f"/registry/pods/p{i}", b"x")
        kv.close()
        kv2 = _durable(tmp_path)
        evs = kv2.events_since(3, "/registry/pods/")
        assert [e.rev for e in evs] == [4, 5, 6]
        assert {e.key for e in evs} == {"/registry/pods/p3",
                                        "/registry/pods/p4",
                                        "/registry/pods/p5"}
        kv2.close()

    def test_compaction_floor_survives_restart(self, tmp_path, backend_kind):
        kv = _durable(tmp_path, backend_kind)
        for i in range(5):
            kv.put(f"/k{i}", b"v")
        kv.compact(3)
        kv.close()
        kv2 = _durable(tmp_path, backend_kind)
        assert kv2.compacted_rev() == 3
        with pytest.raises(native.CompactedError):
            kv2.events_since(2)
        assert [e.rev for e in kv2.events_since(3)] == [4, 5]
        kv2.close()

    @pytest.mark.parametrize("durability", ["off", "batch", "always"])
    def test_every_fsync_policy_recovers(self, tmp_path, durability):
        kv = _durable(tmp_path, durability=durability)
        for i in range(10):
            kv.put(f"/k{i}", str(i).encode())
        kv.close()
        kv2 = _durable(tmp_path, durability=durability)
        assert kv2.rev() == 10
        assert kv2.get("/k9").value == b"9"
        kv2.close()

    def test_bad_durability_policy_rejected(self, tmp_path):
        with pytest.raises(ValueError):
            _durable(tmp_path, durability="fsync-sometimes")


class TestSnapshots:
    def test_snapshot_truncates_log_and_recovers(self, tmp_path):
        kv = _durable(tmp_path)
        for i in range(8):
            kv.put(f"/k{i}", b"v")
        kv.compact(2)
        kv.snapshot()
        d = kv.data_dir
        assert len(wal.list_snapshots(d)) == 1
        # the snapshot rotated to a fresh segment and deleted the old one
        segs = wal.list_segments(d)
        assert len(segs) == 1 and segs[0][0] == 2
        kv.put("/tail", b"t")  # lives in the WAL tail only
        kv.close()

        kv2 = _durable(tmp_path)
        assert kv2.rev() == 9
        assert kv2.get("/k7").mod_rev == 8
        assert kv2.get("/tail").mod_rev == 9
        # events at/below the snapshot rev are NOT persisted: the floor
        # rises to the snapshot (honest 410), the tail replays above it
        assert kv2.compacted_rev() == 8
        with pytest.raises(native.CompactedError):
            kv2.events_since(7)
        assert [e.rev for e in kv2.events_since(8)] == [9]
        kv2.close()

    def test_auto_snapshot_every_n_records(self, tmp_path):
        kv = _durable(tmp_path, snapshot_every=10)
        for i in range(25):
            kv.put(f"/k{i}", b"v")
        assert len(wal.list_snapshots(kv.data_dir)) >= 1
        # old snapshots are pruned with the segments they cover
        assert len(wal.list_snapshots(kv.data_dir)) == 1
        kv.close()
        kv2 = _durable(tmp_path)
        assert kv2.rev() == 25
        kv2.close()

    def test_snapshot_dir_entries_durable_before_pruning(self, tmp_path,
                                                         monkeypatch):
        """The snapshot rename and the fresh segment's creation must be
        durable DIRECTORY entries before the old segments/snapshots are
        unlinked — else machine death can persist the unlinks while losing
        the rename, leaving neither the new snapshot nor the old WAL."""
        events = []
        real = wal._fsync_dir

        def spy(path):
            events.append(set(os.listdir(path)))
            real(path)

        monkeypatch.setattr(wal, "_fsync_dir", spy)
        kv = _durable(tmp_path)
        for i in range(3):
            kv.put(f"/k{i}", b"v")
        events.clear()
        kv.snapshot()
        # some dir sync observed BOTH the new snapshot and the doomed old
        # segment: rename + rotation were durable before any unlink
        assert any(
            any(n.startswith("snap-") for n in ls)
            and wal._seg_name(1) in ls and wal._seg_name(2) in ls
            for ls in events)
        kv.close()

    def test_corrupt_snapshot_refuses_boot(self, tmp_path):
        kv = _durable(tmp_path)
        kv.put("/k", b"v")
        kv.snapshot()
        kv.close()
        _, snap = wal.list_snapshots(str(tmp_path / "store"))[-1]
        data = bytearray(open(snap, "rb").read())
        data[len(wal.SNAP_MAGIC) + 10] ^= 0xFF
        open(snap, "wb").write(bytes(data))
        with pytest.raises(wal.WalCorruptionError):
            _durable(tmp_path)


# --------------------------------------------------------------------- #
# the recovery decision table
# --------------------------------------------------------------------- #


class TestRecoveryDecisionTable:
    def _write3(self, tmp_path):
        kv = _durable(tmp_path)
        for i in range(3):
            kv.put(f"/k{i}", b"v")
        kv.close()
        return wal.list_segments(str(tmp_path / "store"))[-1][1]

    def test_torn_final_record_truncated_cleanly(self, tmp_path):
        seg = self._write3(tmp_path)
        with open(seg, "r+b") as f:
            f.truncate(os.path.getsize(seg) - 3)  # tear the last frame
        kv = _durable(tmp_path)
        assert kv.torn_tail_truncated
        assert kv.rev() == 2          # the torn record is gone...
        assert kv.get("/k2") is None
        assert kv.put("/k2", b"v") == 3  # ...and its revision is REISSUED
        # only after the truncate, never silently skipped
        kv.close()
        kv2 = _durable(tmp_path)      # the truncate itself was durable
        assert not kv2.torn_tail_truncated and kv2.rev() == 3
        kv2.close()

    def test_torn_tail_chaos_seam(self, tmp_path):
        self._write3(tmp_path)
        faultline.install("wal.torn@tail")
        kv = _durable(tmp_path)
        assert faultline.active().fired("wal.torn", "tail") == 1
        assert kv.torn_tail_truncated and kv.rev() == 2
        kv.close()

    def test_midlog_corruption_refuses_boot(self, tmp_path):
        seg = self._write3(tmp_path)
        data = bytearray(open(seg, "rb").read())
        data[wal.SEG_HEADER_LEN + 10] ^= 0xFF  # first frame, bytes follow
        open(seg, "wb").write(bytes(data))
        with pytest.raises(wal.WalCorruptionError) as ei:
            _durable(tmp_path)
        assert "CRC" in str(ei.value)

    def test_corruption_in_nonfinal_segment_refuses_boot(self, tmp_path):
        kv = _durable(tmp_path, segment_bytes=64)  # rotate constantly
        for i in range(6):
            kv.put(f"/k{i}", b"v" * 8)
        kv.close()
        segs = wal.list_segments(str(tmp_path / "store"))
        assert len(segs) >= 3
        first = segs[0][1]
        with open(first, "r+b") as f:  # tear the FIRST segment's tail:
            f.truncate(os.path.getsize(first) - 3)  # not final → corrupt
        with pytest.raises(wal.WalCorruptionError):
            _durable(tmp_path)

    @pytest.mark.parametrize("junk", [b"", b"\x00" * 7,
                                      wal.SEG_MAGIC[:4] + b"\x00"],
                             ids=["empty", "zeros", "partial-magic"])
    def test_headerless_final_segment_two_reboots(self, tmp_path, junk):
        """Crash during rotation: the final segment was created but died
        before its 16-byte header landed. Boot 2 must reset it to a valid
        header — POSIX truncate EXTENDS a shorter file, so truncating "up"
        to SEG_HEADER_LEN pads a corrupt header that boot 3 would refuse,
        losing boot 2's acknowledged (fsynced) writes."""
        kv = _durable(tmp_path)
        kv.put("/k0", b"v")
        kv.close()
        d = str(tmp_path / "store")
        with open(os.path.join(d, wal._seg_name(2)), "wb") as f:
            f.write(junk)

        kv2 = _durable(tmp_path)                 # boot 2
        assert kv2.rev() == 1 and kv2.get("/k0") is not None
        assert kv2.put("/k1", b"w") == 2         # acknowledged + fsynced
        kv2.close()

        kv3 = _durable(tmp_path)                 # boot 3
        assert not kv3.torn_tail_truncated
        assert kv3.rev() == 2
        assert kv3.get("/k1").value == b"w"
        kv3.close()

    def test_disk_full_refuses_append_memory_unchanged(self, tmp_path):
        kv = _durable(tmp_path)
        assert kv.put("/k0", b"v") == 1
        faultline.install("disk.full@wal")
        with pytest.raises(wal.WalWriteError):
            kv.put("/k1", b"v")
        faultline.uninstall()
        # the failed write never happened anywhere: not in memory...
        assert kv.rev() == 1 and kv.get("/k1") is None
        assert kv.put("/k1", b"v") == 2
        kv.close()
        # ...and not on disk
        kv2 = _durable(tmp_path)
        assert kv2.rev() == 2
        kv2.close()


class TestRevContinuityGuard:
    def test_rev_skew_raises_even_under_optimize(self, tmp_path):
        """The WAL/backend revision-continuity check must be a real raise,
        not an `assert` that python -O compiles away: a skew logs one
        revision while the backend assigns another, corrupting replay and
        every resume token."""
        kv = _durable(tmp_path)
        assert kv.put("/k0", b"v") == 1
        orig_put, orig_del = kv._backend.txn_put, kv._backend.txn_delete
        kv._backend.txn_put = lambda *a: 999
        with pytest.raises(wal.WalCorruptionError, match="rev skew"):
            kv.put("/k1", b"v")
        kv._backend.txn_put = orig_put
        kv._backend.txn_delete = lambda *a: 999
        with pytest.raises(wal.WalCorruptionError, match="rev skew"):
            kv.txn_delete("/k0")
        kv._backend.txn_delete = orig_del
        kv.close()


# --------------------------------------------------------------------- #
# proc.crash@wal:* — the apiserver dies mid-commit
# --------------------------------------------------------------------- #


class TestWalCrashSites:
    @pytest.mark.parametrize("site", ["wal:pre_fsync", "wal:post_fsync",
                                      "wal:post_append"])
    def test_crash_mid_commit_record_survives(self, tmp_path, site):
        kv = _durable(tmp_path)
        kv.put("/acked", b"v")  # acknowledged before the kill window
        faultline.install(f"proc.crash@{site}:1")
        with pytest.raises(faultline.InjectedCrash):
            kv.put("/inflight", b"w")
        faultline.uninstall()
        # simulate process death: no clean close of the old incarnation
        kv2 = _durable(tmp_path)
        # the acknowledged write can never be lost; the in-flight record
        # was appended before every crash site, so reboot re-delivers it
        # (committed-but-unacked MAY surface — the etcd contract)
        assert kv2.get("/acked") is not None
        assert kv2.get("/inflight") == native.KVRecord("/inflight", b"w",
                                                       2, 2)
        assert kv2.rev() == 2
        assert kv2.put("/next", b"x") == 3  # strictly monotonic across death
        kv2.close()


# --------------------------------------------------------------------- #
# PyKV ↔ native parity goldens (satellite): one scripted op sequence,
# identical revisions / events / floors — and identical WAL bytes
# --------------------------------------------------------------------- #


def _scripted_ops(kv):
    """Puts, CAS races, deletes, compaction — returns the observable trace."""
    trace = []
    trace.append(kv.txn_put("/registry/pods/ns1/a", 0, b"a1"))
    trace.append(kv.put("/registry/pods/ns1/b", b"b1"))
    trace.append(kv.txn_put("/registry/pods/ns1/a", 0, b"dup"))   # -1
    trace.append(kv.txn_put("/registry/pods/ns1/a", 1, b"a2"))    # CAS ok
    trace.append(kv.txn_put("/registry/pods/ns1/a", 1, b"stale"))  # -1
    trace.append(kv.txn_delete("/registry/pods/ns1/b", 99))       # -1
    trace.append(kv.txn_delete("/registry/pods/ns1/b"))
    for i in range(4):
        trace.append(kv.put(f"/registry/nodes/n{i}", b"n"))
    trace.append(kv.compact(5))
    trace.append(kv.txn_delete("/registry/nodes/n0", 5))
    trace.append(kv.rev())
    trace.append(kv.compacted_rev())
    trace.append([(e.rev, e.type, e.key, e.value)
                  for e in kv.events_since(5)])
    trace.append([(r.key, r.value, r.create_rev, r.mod_rev)
                  for r in kv.range("/registry/")[0]])
    return trace


class TestParityGoldens:
    def test_backends_agree_bare(self):
        assert _scripted_ops(_mk_backend("native")) == \
            _scripted_ops(_mk_backend("python"))

    def test_backends_agree_durable_with_identical_wal_bytes(self, tmp_path):
        kv_n = DurableKV(_mk_backend("native"), str(tmp_path / "n"),
                         durability="always")
        kv_p = DurableKV(_mk_backend("python"), str(tmp_path / "p"),
                         durability="always")
        trace_n, trace_p = _scripted_ops(kv_n), _scripted_ops(kv_p)
        kv_n.close()
        kv_p.close()
        assert trace_n == trace_p
        bytes_n = _wal_bytes(str(tmp_path / "n"))
        assert bytes_n == _wal_bytes(str(tmp_path / "p"))
        assert len(bytes_n) > wal.SEG_HEADER_LEN
        # and the log written by ONE backend recovers into the OTHER
        kv_x = DurableKV(_mk_backend("python"), str(tmp_path / "n"),
                         durability="always")
        assert (kv_x.rev(), kv_x.compacted_rev()) == (trace_n[-4],
                                                      trace_n[-3])
        assert [(r.key, r.value, r.create_rev, r.mod_rev)
                for r in kv_x.range("/registry/")[0]] == trace_n[-1]
        kv_x.close()


# --------------------------------------------------------------------- #
# Storage / APIServer wiring
# --------------------------------------------------------------------- #


class TestStorageWiring:
    def test_storage_boot_recovery_continues_rvs(self, tmp_path):
        d = str(tmp_path / "store")
        st = Storage(data_dir=d, durability="always")
        obj = {"apiVersion": "v1", "kind": "ConfigMap",
               "metadata": {"name": "c", "namespace": "ns"}, "data": {}}
        created = st.create("/registry/core/configmaps/ns/c", obj)
        rv1 = int(created["metadata"]["resourceVersion"])
        st.close()

        st2 = Storage(data_dir=d, durability="always")
        got = st2.get("/registry/core/configmaps/ns/c")
        assert int(got["metadata"]["resourceVersion"]) == rv1
        updated = st2.guaranteed_update(
            "/registry/core/configmaps/ns/c",
            lambda o: {**o, "data": {"k": "v"}})
        assert int(updated["metadata"]["resourceVersion"]) == rv1 + 1
        st2.close()

    def test_watch_resume_across_storage_restart(self, tmp_path):
        from kubernetes_tpu.machinery import watch as mwatch

        d = str(tmp_path / "store")
        st = Storage(data_dir=d, durability="always")
        for i in range(4):
            st.create(f"/registry/pods/ns/p{i}",
                      {"metadata": {"name": f"p{i}", "namespace": "ns"}})
        st.close()

        # a client that consumed through rv=2 resumes on the REBOOTED
        # store and receives exactly the missed tail — no relist, no gap
        st2 = Storage(data_dir=d, durability="always")
        w = st2.watch("/registry/pods/", since_rv="2")
        got = [w.next(timeout=2) for _ in range(2)]
        assert [e.type for e in got] == [mwatch.ADDED, mwatch.ADDED]
        assert [e.object["metadata"]["resourceVersion"] for e in got] == \
            ["3", "4"]
        w.stop()
        st2.close()


class TestBackendVisibility:
    def test_backend_reported_once_with_reason(self, monkeypatch, caplog):
        import logging

        monkeypatch.setattr(native, "_backend_reported", False)
        faultline.install("native.dlopen")
        with caplog.at_level(logging.WARNING, logger="ktpu.storage"):
            kv = native.new_kv()
        faultline.uninstall()
        assert isinstance(kv, PyKV)
        assert native.BACKEND_INFO.value(backend="python",
                                         reason="chaos") == 1
        assert any("PyKV fallback" in r.message for r in caplog.records)
        # once per process: a second new_kv must not re-log
        n_records = len(caplog.records)
        with caplog.at_level(logging.WARNING, logger="ktpu.storage"):
            native.new_kv(prefer_native=False)
        assert len(caplog.records) == n_records

    def test_build_error_captured_for_the_log_line(self, monkeypatch):
        calls = {}

        def boom(*a, **k):
            calls["ran"] = True
            raise OSError("no toolchain")

        monkeypatch.setattr(native.subprocess, "run", boom)
        monkeypatch.setattr(native, "_build_error", None)
        monkeypatch.setattr(native.os.path, "exists", lambda p: False)
        assert native._build_lib() is None
        assert calls.get("ran")
        assert "no toolchain" in native._build_error


# --------------------------------------------------------------------- #
# the cold-restart drill: apiserver dies mid-commit-loop, reboot from
# disk, informers resume by RV with 0 relists, ledger replay reconciles
# to 0 lost / 0 double-bound
# --------------------------------------------------------------------- #


class TestColdRestartDrill:
    N_NODES, N_PODS = 4, 12
    CAPS = {"capacity": {"cpu": "16", "memory": "64Gi", "pods": "110"},
            "allocatable": {"cpu": "16", "memory": "64Gi", "pods": "110"}}

    def _mk_scheduler(self, client, storage, ledger="storage"):
        from kubernetes_tpu.api.v1 import node_from_v1, pod_from_v1
        from kubernetes_tpu.sched.ledger import (APIBindIntentLedger,
                                                 BindIntentLedger)
        from kubernetes_tpu.sched.scheduler import Scheduler
        from kubernetes_tpu.sched.server import APIBinder
        from kubernetes_tpu.state.dims import Dims

        s = Scheduler(binder=APIBinder(client),
                      # "api" (ISSUE 39): the same records, written through
                      # the client as bindintents.ktpu.io
                      ledger=BindIntentLedger(storage) if ledger == "storage"
                      else APIBindIntentLedger(client),
                      base_dims=Dims(N=16, P=16, E=64), batch_size=8)
        for n in client.nodes.list()["items"]:
            s.on_node_add(node_from_v1(n))
        for p in client.pods.list("default")["items"]:
            s.on_pod_add(pod_from_v1(p))
        return s

    def _lookup(self, client):
        from kubernetes_tpu.api.v1 import pod_from_v1
        from kubernetes_tpu.machinery import errors

        def lookup(key):
            ns, name = key.split("/", 1)
            try:
                return pod_from_v1(client.pods.get(name, ns))
            except errors.StatusError:
                return None
        return lookup

    @pytest.mark.parametrize("ledger", ["storage", "api"])
    def test_kill_apiserver_mid_commit_reboot_from_disk(self, tmp_path,
                                                        ledger):
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.client import Client
        from kubernetes_tpu.client.informers import SharedInformer
        from kubernetes_tpu.sched.ledger import BindIntentLedger

        d = str(tmp_path / "store")
        api1 = APIServer(data_dir=d, durability="always")
        client = Client.local(api1)
        for i in range(self.N_NODES):
            client.nodes.create({"apiVersion": "v1", "kind": "Node",
                                 "metadata": {"name": f"n{i}"},
                                 "status": self.CAPS})
        for i in range(self.N_PODS):
            client.pods.create({
                "apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": f"p{i}", "namespace": "default"},
                "spec": {"containers": [{
                    "name": "c", "image": "i",
                    "resources": {"requests": {"cpu": "100m",
                                               "memory": "64Mi"}}}]}})

        informer = SharedInformer(client.pods, namespace="default")
        informer.start()
        assert informer.wait_for_sync(10)
        relists0 = informer.relists

        s1 = self._mk_scheduler(client, api1.storage, ledger)
        # the kill lands on the SECOND wal append after arming: the wave's
        # intent is durable, the first Binding just committed — the
        # apiserver dies mid-commit-loop with the response never returned
        faultline.install("proc.crash@wal:post_append:2")
        with pytest.raises(faultline.InjectedCrash):
            s1.schedule_pending()
        faultline.uninstall()
        rev_at_death = api1.storage.kv.rev()
        assert len(BindIntentLedger(api1.storage).unretired()) == 1

        # the process is gone: quiesce the informer (it records its resume
        # token) and the dead server's pump; nothing flushes the WAL
        informer.stop()
        api1.storage._stop.set()

        # ---- reboot from disk ---------------------------------------- #
        api2 = APIServer(data_dir=d, durability="always")
        assert api2.storage.kv.recovered
        # RV continuity: the reborn counter continues the dead process's
        # sequence — never reissues
        assert api2.storage.kv.rev() == rev_at_death

        # informers resume by RV with 0 relists: same informer object (its
        # indexer + last_sync_rv survived, like a reflector whose server
        # bounced), transport re-pointed at the reborn server
        client.transport.api = api2
        informer.start()
        assert informer.wait_for_sync(10)
        assert informer.relists == relists0, "resume fell back to relist"

        # the reborn apiserver still holds the bind intents: a successor
        # scheduler replays the ledger to 0 lost / 0 double-bound
        s2 = self._mk_scheduler(client, api2.storage, ledger)
        report = s2.recover(lookup=self._lookup(client))
        assert report.replayed_intents == 1
        s2.run_until_idle()

        # a resume is only COUNTED once the re-established stream delivers
        # its first signal — the successor's Binding commits provide it
        deadline = time.monotonic() + 5
        while informer.resumes < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert informer.resumes >= 1
        assert informer.relists == relists0, "post-resume relist crept in"

        pods = client.pods.list("default")["items"]
        bound = [p for p in pods if p.get("spec", {}).get("nodeName")]
        assert len(pods) == self.N_PODS
        assert len(bound) == self.N_PODS, (
            f"lost pods after cold restart: {self.N_PODS - len(bound)}")
        assert s2.ledger.unretired() == []
        assert api2.storage.kv.rev() > rev_at_death  # still monotonic
        informer.stop()
        api2.close()
