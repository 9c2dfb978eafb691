"""Flight recorder + e2e latency telemetry (ISSUE 7; sched/telemetry.py,
docs/OBSERVABILITY.md).

Everything runs under deterministic clocks: the SCHEDULER clock (the
queue/event time domain the e2e stamps live in) and the TELEMETRY clock
(the phase-span domain) are injected separately, so phase ordering, ring
eviction, first-seen-across-requeue and dump-on-abandon are all asserted
exactly — no sleeps, no wall-time flakes.
"""

import dataclasses
import gc
import json
import logging
import threading

import pytest

from kubernetes_tpu.api.types import Pod, Resources
from kubernetes_tpu.component.metrics import Counter, Histogram, Registry
from kubernetes_tpu.component.trace import Trace
from kubernetes_tpu.models.workloads import make_nodes
from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler
from kubernetes_tpu.sched.telemetry import (
    WAVE_PHASES,
    FlightRecorder,
    PodLatencyTracker,
    SchedulerTelemetry,
)
from kubernetes_tpu.utils import faultline

pytestmark = pytest.mark.latency


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faultline.uninstall()


def _pod(i, **kw):
    return Pod(name=f"p{i}",
               requests=Resources.make(cpu="10m", memory="8Mi"),
               creation_index=i, **kw)


def _scheduler(clk, batch_size=64):
    s = Scheduler(binder=RecordingBinder(), batch_size=batch_size,
                  clock=lambda: clk["t"])
    for n in make_nodes(8):
        s.on_node_add(n)
    return s


# --------------------------------------------------------------------- #
# satellite: component/trace.py threshold + exception semantics
# --------------------------------------------------------------------- #

class TestTraceFix:
    def test_threshold_is_constructor_arg(self, caplog):
        t = [0.0]
        with caplog.at_level(logging.WARNING, logger="kubernetes_tpu.trace"):
            with Trace("slow-but-allowed", clock=lambda: t[0],
                       threshold=5.0):
                t[0] = 1.0  # over the old hardcoded 0.1, under ours
        assert not caplog.records
        with caplog.at_level(logging.WARNING, logger="kubernetes_tpu.trace"):
            with Trace("slow", clock=lambda: t[0], threshold=0.5) as tr:
                tr.step("work")
                t[0] = 2.0
        assert any("slow" in r.message for r in caplog.records)

    def test_exception_exit_skips_log_if_long(self, caplog):
        t = [0.0]
        with caplog.at_level(logging.WARNING, logger="kubernetes_tpu.trace"):
            with pytest.raises(RuntimeError):
                with Trace("doomed", clock=lambda: t[0], threshold=0.01):
                    t[0] = 99.0  # way over threshold — but we raise
                    raise RuntimeError("the failure path already reports")
        assert not caplog.records


# --------------------------------------------------------------------- #
# tier 1: first-seen tracker
# --------------------------------------------------------------------- #

class TestPodLatencyTracker:
    def test_first_seen_is_idempotent(self):
        tr = PodLatencyTracker()
        tr.stamp("a/x", 1.0)
        tr.stamp("a/x", 5.0)   # a requeue must NOT move the stamp
        assert tr.pop_latency("a/x", 11.0) == 10.0
        assert tr.pop_latency("a/x", 12.0) is None  # consumed

    def test_discard(self):
        tr = PodLatencyTracker()
        tr.stamp("a/x", 1.0)
        tr.discard("a/x")
        assert tr.pop_latency("a/x", 2.0) is None
        assert len(tr) == 0


# --------------------------------------------------------------------- #
# tier 2: flight recorder ring
# --------------------------------------------------------------------- #

class TestFlightRecorder:
    def test_ring_eviction(self):
        fr = FlightRecorder(capacity=4)
        for i in range(6):
            fr.record({"marker": i})
        recs = fr.records()
        assert [r["marker"] for r in recs] == [2, 3, 4, 5]
        assert [r["seq"] for r in recs] == [3, 4, 5, 6]
        assert fr.evicted == 2
        snap = fr.snapshot("manual")
        assert snap["trigger"] == "manual"
        assert snap["last_seq"] == 6
        assert len(snap["records"]) == 4
        json.dumps(snap)  # the dump document must be pure JSON


# --------------------------------------------------------------------- #
# wave spans through the real scheduler
# --------------------------------------------------------------------- #

class TestWaveSpans:
    def test_phase_span_ordering_and_durations(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        # telemetry clock: +1ms per observation, so every phase gets a
        # strictly positive, exactly-known duration
        tick = {"n": 0}

        def tel_clock():
            tick["n"] += 1
            return tick["n"] * 0.001

        s.telemetry.clock = tel_clock
        for i in range(5):
            s.on_pod_add(_pod(i))
        st = s.schedule_pending()
        assert st.scheduled == 5
        rec = s.telemetry.recorder.records()[-1]
        names = [p for p, _ in rec["phases"]]
        # the serving order, exactly (a healthy wave marks every phase)
        assert names == ["pump", "pop", "snapshot", "prewarm", "dispatch",
                         "readback", "intent-write", "bind-commit",
                         "retire", "requeue"]
        assert set(names) <= set(WAVE_PHASES)
        assert all(dt > 0 for _, dt in rec["phases"])
        assert rec["stats"]["scheduled"] == 5
        assert rec["bucket"]["N"] >= 8
        # tier 3 rode along on the primary dispatch
        assert set(rec["device_split"]) == {"launch_s", "execute_s",
                                            "readback_s"}

    def test_e2e_histogram_and_per_phase_series_fed(self):
        from kubernetes_tpu.sched.metrics import (POD_E2E_LATENCY,
                                                  SCHEDULING_DURATION)

        clk = {"t": 0.0}
        s = _scheduler(clk)
        before = POD_E2E_LATENCY.count()
        phase_before = SCHEDULING_DURATION.count(operation="snapshot")
        for i in range(3):
            s.on_pod_add(_pod(i))
        clk["t"] = 2.0
        s.schedule_pending()
        assert POD_E2E_LATENCY.count() == before + 3
        assert SCHEDULING_DURATION.count(operation="snapshot") == \
            phase_before + 1

    def test_disabled_telemetry_is_a_noop(self):
        clk = {"t": 0.0}
        s = Scheduler(binder=RecordingBinder(), batch_size=64,
                      clock=lambda: clk["t"])
        s.telemetry = SchedulerTelemetry(enabled=False)
        s.queue.tracker = None
        for n in make_nodes(4):
            s.on_node_add(n)
        s.on_pod_add(_pod(0))
        st = s.schedule_pending()
        assert st.scheduled == 1
        assert s.telemetry.recorder.records() == []
        assert len(s.telemetry.latency_samples) == 0


# the record of each kind of wave, as benchmarks/harness reads it: phase
# names in order, the children paths beneath them, and the record's keys
# in order. What the stages of `Scheduler._run_wave` must keep writing.
_BULK = ["pump", "pop", "snapshot", "prewarm", "dispatch", "readback",
         "intent-write", "bind-commit", "retire", "requeue"]
_BINDING = ["bind-commit/assume", "bind-commit/bind-call",
            "bind-commit/finish"]
_FIRST_SNAPSHOT = ["snapshot/full", "snapshot/full/upload",
                   "snapshot/pins", "snapshot/prepare"]
_HEAD = ["recorder", "t_start", "duration_s", "phases", "engine"]
# what the collector did since the previous record (ISSUE 37): after the
# record's own fields, before what the caller adds
_GC = ["gc_full_collections", "gc_pause_s", "gc_max_pause_s",
       "gc_max_pause_at",
       # and what XLA cost the process (ISSUE 51); `xla_compiled` follows
       # on the record of a wave that compiled, which depends on what this
       # process ran before: the shape below is held without it
       "xla_total"]
# what a preemption pass did (ISSUE 41): on the record of a wave whose pass
# had an eligible pod, candidates or none
_PASS = ["preempt_lanes", "preempt_preemptors", "preempt_dispatches",
         "preempt_nodes_handed_out", "preempt_nominated", "preempt_victims",
         "preempt_retry_soon", "preempt_nominate_s", "preempt_evict_s"]
# the assumed set at the pop, and the size of the wave's expiry pass over
# it (ISSUE 46): pods `cleanup` looked at, pods it dropped
_ASSUMED = ["assumed_outstanding", "assumed_examined", "assumed_expired"]
_WAVE_SHAPES = {
    # kind: (phases, children, keys)
    "bulk": (_BULK,
             _BINDING + ["requeue/preempt", "requeue/preempt/what-if",
                         "requeue/snapshot", "requeue/snapshot/patch",
                         "requeue/snapshot/patch/upload",
                         "requeue/snapshot/prepare"] + _FIRST_SNAPSHOT,
             _HEAD + ["bucket", "affinity_agg", "domain_sum", "stats",
                      "device_split",
                      "children"] + _GC + ["snapshot_mode", "waits"]
             + _ASSUMED + _PASS + ["minor_faults", "seq"]),
    "micro": (_BULK,
              _BINDING + _FIRST_SNAPSHOT + ["snapshot/upload"],
              _HEAD + ["micro", "bucket", "affinity_agg", "domain_sum",
                       "stats", "device_split", "children"] + _GC + [
                           "snapshot_mode", "waits"] + _ASSUMED + [
                           "minor_faults", "seq"]),
    # a batch with pinned pods (ISSUE 49): how many, the classes that hold
    # them and the live class count from the snapshot's `pins` stage; after
    # the readback how many their node refused and the engine's rounds
    "pinned": (_BULK,
               _BINDING + _FIRST_SNAPSHOT,
               _HEAD + ["bucket", "affinity_agg", "domain_sum", "stats",
                        "device_split",
                        "children"] + _GC + ["snapshot_mode", "waits"]
               + _ASSUMED + ["pinned", "pin_classes", "classes",
                             "pinned_unfit", "pin_rounds", "minor_faults",
                             "seq"]),
    "paused": (["pump", "paused"], None,
               _HEAD + ["stats", "supervisor_events"] + _GC + ["seq"]),
    "abandoned": (["pump", "pop", "snapshot", "prewarm", "dispatch",
                   "readback", "requeue"],
                  _FIRST_SNAPSHOT,
                  _HEAD + ["bucket", "affinity_agg", "domain_sum", "stats",
                           "supervisor_events", "children"] + _GC + [
                               "waits"] + _ASSUMED + ["seq"]),
    "raises": (_BULK[:8] + ["exception"],
               _BINDING + _FIRST_SNAPSHOT,
               _HEAD + ["bucket", "affinity_agg", "domain_sum", "stats",
                        "device_split",
                        "children"] + _GC + ["waits"] + _ASSUMED + [
                            "exception", "seq"]),
}


def _without_compiles(rec):
    """The record's keys less what an on-path compile adds to the record of
    the wave that waited for it: `xla_compiled`, and `supervisor_events`
    where every event is a `compile`."""
    events = rec.get("supervisor_events", [("", "")])
    return [k for k in rec if k != "xla_compiled" and not (
        k == "supervisor_events"
        and all(kind == "compile" for kind, _detail in events))]


class TestRecordShape:
    @pytest.mark.parametrize("kind", sorted(_WAVE_SHAPES))
    def test_each_kind_of_wave_keeps_its_record(self, kind):
        from kubernetes_tpu.sched.preemption import Preemptor

        phases, children, keys = _WAVE_SHAPES[kind]
        clk = {"t": 0.0}
        s = Scheduler(binder=RecordingBinder(), batch_size=64,
                      clock=lambda: clk["t"], microwave=(kind == "micro"),
                      preemptor=Preemptor() if kind == "bulk" else None)
        for n in make_nodes(8):
            s.on_node_add(n)
        for i in range(3):
            s.on_pod_add(_pod(i))
        if kind == "bulk":
            # one pod no node holds: the preemption pass runs under requeue
            s.on_pod_add(Pod(name="huge", creation_index=9, priority=5,
                             requests=Resources.make(cpu="4000",
                                                     memory="8Mi")))
        elif kind == "pinned":
            from kubernetes_tpu.api.types import (Affinity, NodeSelector,
                                                  NodeSelectorTerm)
            for i, node in enumerate(("node-1", "node-2", "gone")):
                s.on_pod_add(Pod(
                    name=f"daemon-{i}", creation_index=20 + i,
                    requests=Resources.make(cpu="100m", memory="8Mi"),
                    affinity=Affinity(node_required=NodeSelector(
                        (NodeSelectorTerm((), (node,)),)))))
        elif kind == "paused":
            for _ in range(5):
                s.governor.note_commit(False, 0.01)
        elif kind == "abandoned":
            faultline.install("device.error@cycle:1,device.fallback@cycle:1")
        if kind == "raises":
            s._retire_intent = lambda intent: 1 / 0
            with pytest.raises(ZeroDivisionError):
                s.schedule_pending()
        else:
            st = s.schedule_pending()
            assert st.micro == (1 if kind == "micro" else 0)
        rec = s.telemetry.recorder.records()[-1]
        assert [p for p, _ in rec["phases"]] == phases
        assert (sorted(rec["children"]) if "children" in rec else None) \
            == children
        assert _without_compiles(rec) == keys
        if kind == "pinned":
            assert [rec[k] for k in ("pinned", "pin_classes", "pinned_unfit",
                                     "pin_rounds")] == [3, 1, 1, 1]
            assert rec["classes"] == len(s.encoder.class_reg)
        if "waits" in rec:
            assert set(rec["waits"]) == {"queue", "confirm"}
        if "bucket" in rec:
            assert rec["domain_sum"] == "product"

    @pytest.mark.parametrize("engine,dims,want", [
        ("waves", dict(N=5120, S=72, SC=64), "product"),
        ("extender", dict(N=5120, S=72, SC=64, P=8), "product"),
        ("scan", dict(N=1024), "product"),
        # four [N, N] bf16 matrices past 2 GiB: the scatter form stays
        ("waves", dict(N=53248, S=72, SC=64), "scatter"),
        # a fleet tick's dispatches stack their own numbers of tenants
        ("fleet", dict(N=1024), None),
    ])
    def test_record_says_how_its_program_sums_over_domains(
            self, engine, dims, want):
        """`domain_sum` beside `affinity_agg`, from the same Dims
        (state/dims.py domain_sum): which form of the in-domain sum the
        record's compiled program runs."""
        from kubernetes_tpu.state.dims import Dims

        tel = SchedulerTelemetry(enabled=True)
        span = tel.wave_span()
        span.mark("pump")
        rec = tel.finish_wave(span, engine=engine, dims=Dims(**dims))
        assert rec.get("domain_sum") == want
        assert rec["bucket"]["N"] == dims["N"]


class TestFirstSeenAcrossRequeue:
    def test_stamp_survives_unschedulable_backoff_round_trip(self):
        """A pod that parks unschedulable, waits out a cluster event and
        binds later must record ingest→bind, not last-requeue→bind."""
        from kubernetes_tpu.api.types import Node

        clk = {"t": 0.0}
        s = _scheduler(clk)
        # nodeSelector no node satisfies: the first wave verdicts the pod
        # unschedulable and parks it
        s.on_pod_add(_pod(0, node_selector={"pool": "later"}))
        st = s.schedule_pending()
        assert st.unschedulable == 1
        assert len(s.telemetry.latency_samples) == 0
        # the matching node arrives much later (move_all_to_active) and
        # the pod finally binds
        clk["t"] = 40.0
        s.on_node_add(Node(name="late", labels={"pool": "later"},
                           allocatable=Resources.make(cpu="8",
                                                      memory="16Gi",
                                                      pods=110)))
        clk["t"] = 50.0
        st = s.schedule_pending()
        assert st.scheduled == 1
        assert s.telemetry.latency_samples[-1] == pytest.approx(50.0)

    def test_prompt_retry_keeps_stamp(self):
        tr_clk = {"t": 3.0}
        s = _scheduler(tr_clk)
        p = _pod(0)
        s.queue.add(p, now=3.0)
        s.queue.pop_batch(10, now=4.0)
        s.queue.add_prompt_retry(p, attempts=1, now=7.0)
        assert s.telemetry.tracker.first_seen(p.key) == 3.0

    def test_deleted_pending_pod_discards_stamp(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        p = _pod(0)
        s.on_pod_add(p)
        s.on_pod_delete(p)
        assert s.telemetry.tracker.first_seen(p.key) is None


# --------------------------------------------------------------------- #
# dump-on-abandon: the acceptance drill — reconstruct the tick from the
# artifact alone
# --------------------------------------------------------------------- #

@pytest.mark.chaos
class TestDumpOnAbandon:
    def test_abandoned_dispatch_dumps_a_reconstructable_record(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        for i in range(7):
            s.on_pod_add(_pod(i))
        faultline.install("device.error@cycle:1,device.fallback@cycle:1")
        st = s.schedule_pending()
        assert st.aborted == 7 and st.scheduled == 0
        dump = s.telemetry.last_dump
        assert dump is not None and dump["trigger"] == "abandoned"
        doc = json.loads(json.dumps(dump))  # structured JSON end to end
        rec = doc["records"][-1]
        # the tick reconstructs WITHOUT logs: what ran (phase spans up to
        # the readback that failed), what the supervisor did (degrade →
        # abandon), and what happened to every popped pod (all requeued)
        names = [p for p, _ in rec["phases"]]
        assert names[:5] == ["pump", "pop", "snapshot", "prewarm",
                             "dispatch"]
        assert "readback" in names and "requeue" in names
        assert "bind-commit" not in names  # nothing committed
        kinds = [k for k, _ in rec["supervisor_events"]]
        assert "degraded" in kinds and "abandoned" in kinds
        assert rec["stats"]["attempted"] == 7
        assert rec["stats"]["aborted"] == 7
        assert rec["stats"]["scheduled"] == 0
        from kubernetes_tpu.sched.metrics import FLIGHT_DUMPS

        assert FLIGHT_DUMPS.value(trigger="abandoned") >= 1

    def test_dump_to_file(self, tmp_path):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        s.on_pod_add(_pod(0))
        s.schedule_pending()
        path = tmp_path / "flight.json"
        doc = s.telemetry.dump("manual", path=str(path))
        on_disk = json.loads(path.read_text())
        assert on_disk["trigger"] == "manual"
        assert on_disk["last_seq"] == doc["last_seq"]
        assert on_disk["records"]


class TestFlightArtifactCaps:
    """ISSUE 20 satellite: FLIGHT_rNN.json bloat — per-record payload caps
    at serialization time plus the record-per-line (optionally gzipped)
    dump format. The in-memory ring keeps full records."""

    def test_fleet_map_caps_to_busiest_with_aggregate(self):
        from kubernetes_tpu.sched.telemetry import _cap_record

        rec = {"fleet": {f"t{i:02d}": {"attempted": i, "scheduled": i}
                         for i in range(12)}}
        out = _cap_record(rec)
        assert len(out["fleet"]) == 9          # 8 busiest + "..."
        agg = out["fleet"]["..."]
        assert agg["tenants_omitted"] == 4
        # busiest by attempted kept (t11..t04); the quiet tail aggregates
        assert agg["attempted"] == 0 + 1 + 2 + 3
        assert "t11" in out["fleet"] and "t00" not in out["fleet"]
        assert len(rec["fleet"]) == 12         # source record untouched

    def test_event_list_caps_head_and_tail_around_marker(self):
        from kubernetes_tpu.sched.telemetry import _cap_record

        ev = [(f"k{i}", "d") for i in range(100)]
        out = _cap_record({"supervisor_events": ev})
        capped = out["supervisor_events"]
        assert len(capped) == 32
        assert capped[0] == ("k0", "d") and capped[-1] == ("k99", "d")
        marker = capped[16]
        assert marker[0] == "truncated" and "omitted" in marker[1]

    def test_under_cap_records_pass_through_unchanged(self):
        from kubernetes_tpu.sched.telemetry import _cap_record

        rec = {"fleet": {"t00": {"attempted": 3}},
               "supervisor_events": [("storm", "t00")], "rc": 1}
        assert _cap_record(rec) == rec

    def test_caps_are_env_tunable_and_clamped(self, monkeypatch):
        from kubernetes_tpu.sched.telemetry import _cap_record

        monkeypatch.setenv("KTPU_FLIGHT_FLEET_CAP", "2")
        rec = {"fleet": {f"t{i}": {"attempted": i} for i in range(5)}}
        assert len(_cap_record(rec)["fleet"]) == 3   # 2 + "..."
        monkeypatch.setenv("KTPU_FLIGHT_FLEET_CAP", "garbage")
        assert len(_cap_record(rec)["fleet"]) == 5   # default cap 8: all

    def test_dump_is_record_per_line_and_reconstructable(self, tmp_path):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        for i in range(5):
            s.on_pod_add(_pod(i))
            s.schedule_pending()
        path = tmp_path / "flight.json"
        doc = s.telemetry.dump("manual", path=str(path))
        text = path.read_text()
        on_disk = json.loads(text)                   # still ONE json object
        assert on_disk["last_seq"] == doc["last_seq"]
        assert len(on_disk["records"]) == len(doc["records"])
        # the bloat fix itself: one line per record, not one per scalar
        rec_lines = [ln for ln in text.splitlines() if ln.startswith("  ")]
        assert len(rec_lines) == len(on_disk["records"])
        assert len(text.splitlines()) <= len(on_disk["records"]) + 16

    def test_gzip_policy_for_flight_dir_dumps(self, tmp_path, monkeypatch):
        import gzip as _gzip
        import os as _os

        monkeypatch.setenv("KTPU_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("KTPU_FLIGHT_GZIP", "1")
        clk = {"t": 0.0}
        s = _scheduler(clk)
        s.on_pod_add(_pod(0))
        s.schedule_pending()
        doc = s.telemetry.dump("manual")
        files = [f for f in _os.listdir(tmp_path) if f.endswith(".json.gz")]
        assert len(files) == 1
        with _gzip.open(tmp_path / files[0], "rt") as f:
            on_disk = json.load(f)
        assert on_disk["last_seq"] == doc["last_seq"]
        assert on_disk["records"]


@pytest.mark.chaos
@pytest.mark.fleet
class TestFleetStormDump:
    def test_storm_degraded_tick_dumps_with_tenant_attribution(self):
        from kubernetes_tpu.fleet import FleetServer
        from kubernetes_tpu.state.dims import Dims

        clk = {"t": 0.0}
        srv = FleetServer(batch_size=32, base_dims=Dims(N=8, P=32, E=64),
                          clock=lambda: clk["t"])
        srv.prewarmer.enabled = False
        nodes = make_nodes(4)
        for k in range(2):
            t = srv.add_tenant(f"t{k:02d}")
            for n in nodes:
                t.on_node_add(n)
            for i in range(6):
                t.on_pod_add(Pod(name=f"t{k}-p{i}",
                                 requests=Resources.make(cpu="10m",
                                                         memory="8Mi"),
                                 creation_index=i))
        srv.tick()
        clk["t"] += 1.0
        faultline.install("tenant.storm@t00:1")
        tk = srv.tick()
        assert tk.per_tenant["t00"].degraded == 1
        dump = srv.telemetry.last_dump
        assert dump is not None and dump["trigger"] == "storm"
        rec = dump["records"][-1]
        # (a solo tenant's first dispatch may compile: ISSUE 51 narrates
        # that beside the storm, on the same record)
        assert [tuple(e) for e in rec["supervisor_events"]
                if e[0] != "compile"] == [("storm", "t00")]
        # per-tenant attribution on the record itself: ONLY t00 degraded
        assert rec["fleet"]["t00"]["degraded"] == 1
        assert rec["fleet"]["t01"]["degraded"] == 0


class TestCrashedAndIdleWaves:
    def test_exception_escaping_the_wave_still_records_and_dumps(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        s.on_pod_add(_pod(0))

        def boom(pending):
            raise ValueError("encode exploded")

        s._snapshot_keys = boom
        with pytest.raises(ValueError):
            s.schedule_pending()
        rec = s.telemetry.recorder.records()[-1]
        assert rec["exception"] is True
        names = [p for p, _ in rec["phases"]]
        assert names[:2] == ["pump", "pop"] and names[-1] == "exception"
        assert rec["stats"]["attempted"] == 1
        assert s.telemetry.last_dump["trigger"] == "exception"

    def test_idle_wave_drains_pending_supervisor_events(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        # e.g. a prewarm compile failure / prober recovery while idle
        s.telemetry.note_supervisor_event("recovery", "prober re-admitted")
        st = s.schedule_pending()     # empty queue
        assert st.attempted == 0
        rec = s.telemetry.recorder.records()[-1]
        assert rec["engine"] == "idle"
        assert ("recovery", "prober re-admitted") in rec["supervisor_events"]
        # event-free idle waves record nothing — the ring stays signal
        n = len(s.telemetry.recorder.records())
        s.schedule_pending()
        assert len(s.telemetry.recorder.records()) == n

    def test_zombie_device_split_never_attaches_to_a_later_wave(self):
        tel = SchedulerTelemetry(enabled=True)
        span = tel.wave_span()
        span.mark("pump")
        # a long-abandoned wave's worker reports with ITS span as token
        tel.note_device_split(60.0, 60.0, 0.1, token=object())
        rec = tel.finish_wave(span, engine="waves")
        assert "device_split" not in rec
        # the live wave's own report (matching token) does attach
        span2 = tel.wave_span()
        span2.mark("pump")
        tel.note_device_split(0.1, 0.2, 0.01, token=span2)
        rec2 = tel.finish_wave(span2, engine="waves")
        assert rec2["device_split"]["execute_s"] == 0.2


# --------------------------------------------------------------------- #
# fleet satellite: DRF clamp lands in the tenant-labelled metric through
# CycleStats → observe_fleet_tick
# --------------------------------------------------------------------- #

@pytest.mark.fleet
class TestDrfClampedMetric:
    def test_clamp_routes_through_cyclestats_to_metric(self):
        from kubernetes_tpu.fleet import FleetServer
        from kubernetes_tpu.sched.metrics import DRF_CLAMPED
        from kubernetes_tpu.state.dims import Dims

        clk = {"t": 0.0}
        srv = FleetServer(batch_size=32, base_dims=Dims(N=8, P=32, E=64),
                          clock=lambda: clk["t"])
        srv.prewarmer.enabled = False
        nodes = make_nodes(4)
        # tenant 0 under a quota that funds roughly half its backlog (the
        # dominant demand at this shape is the implicit pod slot)
        n_pods = 8
        tight = (n_pods / 2) * (1.0 / (len(nodes) * 110.0))
        for k, quota in ((0, tight), (1, 1.0)):
            t = srv.add_tenant(f"q{k:02d}", quota=quota)
            for n in nodes:
                t.on_node_add(n)
            for i in range(n_pods):
                t.on_pod_add(Pod(name=f"q{k}-p{i}",
                                 requests=Resources.make(cpu="10m",
                                                         memory="8Mi"),
                                 creation_index=i))
        before = DRF_CLAMPED.value(tenant="q00")
        before_other = DRF_CLAMPED.value(tenant="q01")
        tk = srv.tick()
        assert tk.per_tenant["q00"].drf_clamped >= 1
        assert tk.per_tenant["q01"].drf_clamped == 0
        assert DRF_CLAMPED.value(tenant="q00") - before == \
            tk.per_tenant["q00"].drf_clamped
        assert DRF_CLAMPED.value(tenant="q01") == before_other
        assert DRF_CLAMPED.total() >= DRF_CLAMPED.value(tenant="q00")


# --------------------------------------------------------------------- #
# satellite: metrics registry thread-safety hammer
# --------------------------------------------------------------------- #

class TestMetricsConcurrency:
    def test_no_lost_increments_under_hammer(self):
        reg = Registry()
        c = reg.counter("hammer_total", labels=("who",))
        h = reg.histogram("hammer_seconds")
        g = reg.gauge("hammer_gauge")
        n_threads, n_iter = 8, 2000
        start = threading.Barrier(n_threads)

        def worker(i):
            start.wait()
            for k in range(n_iter):
                c.inc(who=f"w{i % 2}")
                h.observe(0.01 * (k % 7))
                g.inc()
                g.dec(0.5)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value(who="w0") == n_threads // 2 * n_iter
        assert c.value(who="w1") == n_threads // 2 * n_iter
        assert c.total() == n_threads * n_iter
        assert h.count() == n_threads * n_iter
        assert g.value() == pytest.approx(n_threads * n_iter * 0.5)
        # exposition is consistent under the same locks
        text = reg.expose_text()
        assert f"hammer_seconds_count {n_threads * n_iter}" in text

    def test_registry_register_is_idempotent_under_races(self):
        reg = Registry()
        out = []
        barrier = threading.Barrier(8)

        def worker():
            barrier.wait()
            out.append(reg.counter("same_name"))

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(m is out[0] for m in out)


# --------------------------------------------------------------------- #
# device split + quantiles
# --------------------------------------------------------------------- #

class TestQuantilesAndSplit:
    def test_latency_quantiles_exact(self):
        tel = SchedulerTelemetry(enabled=True)
        for v in (0.001, 0.002, 0.003, 0.004, 1.0):
            tel.latency_samples.append(v)
        q = tel.latency_quantiles((0.5, 0.99))
        assert q[0.5] == 0.003
        assert q[0.99] == 1.0

    def test_histogram_quantile_buckets(self):
        from kubernetes_tpu.component.metrics import Histogram

        h = Histogram("q_test", "")
        for v in (0.003, 0.003, 0.003, 0.9):
            h.observe(v)
        assert h.quantile(0.5) == 0.005   # bucket upper bound
        assert h.quantile(0.99) == 1.0


# --------------------------------------------------------------------- #
# ISSUE 24: spans where the chip sits idle — children of a phase, the
# server loop between waves, what a pod waited for
# --------------------------------------------------------------------- #

from kubernetes_tpu.component import trace as ktrace  # noqa: E402


class _SpyBinder(RecordingBinder):
    """Records what `trace.current()` is where a callee would ask: inside
    the Binding, on the wave's thread and on a thread started there."""

    def __init__(self):
        super().__init__()
        self.seen = []

    def bind(self, pod, node_name):
        here = ktrace.current()
        other = []
        t = threading.Thread(target=lambda: other.append(ktrace.current()))
        t.start()
        t.join()
        self.seen.append((here, other[0]))
        return super().bind(pod, node_name)


class TestChildSpans:
    def test_paths_nest_and_self_time_is_total_less_children(self):
        tr = Trace("wave", clock=lambda: 0.0)
        for _ in range(3):
            tok = tr.begin("bind-call")
            inner = tr.begin("apiserver.bind")
            tr.child("store.txn", 0.2)
            tr.end(inner, 0.5)
            tr.end(tok, 0.75)
            tr.child("finish", 0.25)
        tr.step("bind-commit")      # the step that closes the phase names it
        tr.child("orphan", 1.0)     # no step yet: listed without a phase
        ch = tr.children()
        assert list(ch) == [
            "bind-commit/bind-call",
            "bind-commit/bind-call/apiserver.bind",
            "bind-commit/bind-call/apiserver.bind/store.txn",
            "bind-commit/finish", "orphan"]
        assert ch["bind-commit/bind-call"] == [3, 2.25, 0.75]
        assert ch["bind-commit/bind-call/apiserver.bind/store.txn"] == \
            pytest.approx([3, 0.6, 0.2])
        # a layer's self time: its total less its direct children's
        api = ch["bind-commit/bind-call/apiserver.bind"][1]
        assert api - ch["bind-commit/bind-call/apiserver.bind/store.txn"][1] \
            == pytest.approx(0.9)

    def test_slash_path_files_below_a_parent_without_calling_it(self):
        tr = Trace("op", clock=lambda: 0.0)
        tr.child("store.txn", 1.0)
        tr.child("store.txn/kv", 0.25)
        assert tr.children() == {"store.txn": [1, 1.0, 1.0],
                                 "store.txn/kv": [1, 0.25, 0.25]}

    def test_same_phase_twice_merges(self):
        tr = Trace("op", clock=lambda: 0.0)
        tr.child("x", 1.0)
        tr.step("phase")
        tr.child("x", 3.0)
        tr.step("phase")
        assert tr.children() == {"phase/x": [2, 4.0, 3.0]}

    def test_ten_thousand_commits_leave_a_fixed_small_record(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        nodes = [n.name for n in s.cache.nodes()]
        span = s.telemetry.wave_span()
        token = ktrace.activate(span.trace)
        try:
            from kubernetes_tpu.sched.scheduler import CycleStats
            stats = CycleStats(attempted=10_000)
            for i in range(10_000):
                s._commit(_pod(i), nodes[i % len(nodes)], 1, 0.0, 1, stats)
            span.mark("bind-commit")
        finally:
            ktrace.deactivate(token)
        rec = s.telemetry.finish_wave(span, stats=stats)
        assert stats.scheduled == 10_000
        assert sorted(rec["children"]) == [
            "bind-commit/assume", "bind-commit/bind-call",
            "bind-commit/finish"]
        assert all(v[0] == 10_000 for v in rec["children"].values())
        # the three parts and the phase's own remainder make up the phase
        phase = dict(rec["phases"])["bind-commit"]
        parts = sum(v[1] for v in rec["children"].values())
        assert 0 <= phase - parts < phase

    def test_current_is_the_waves_trace_only_on_its_thread(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        s.binder = spy = _SpyBinder()
        assert ktrace.current() is None
        s.on_pod_add(_pod(0))
        assert s.schedule_pending().scheduled == 1
        (here, elsewhere), = spy.seen
        assert isinstance(here, Trace) and elsewhere is None
        assert ktrace.current() is None          # and gone after the wave
        rec = s.telemetry.recorder.records()[-1]
        assert rec["children"]["bind-commit/bind-call"][0] == 1

    def test_current_is_cleared_when_the_wave_raises(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        s.on_pod_add(_pod(0))
        s._retire_intent = lambda intent: 1 / 0
        with pytest.raises(ZeroDivisionError):
            s.schedule_pending()
        assert ktrace.current() is None
        rec = s.telemetry.recorder.records()[-1]
        assert rec["exception"] and "waits" in rec   # the pop's readings kept

    def test_kill_switch_no_trace_no_field(self, monkeypatch):
        monkeypatch.setenv("KTPU_TELEMETRY", "0")
        clk = {"t": 0.0}
        s = _scheduler(clk)
        assert not s.telemetry.enabled
        s.binder = spy = _SpyBinder()
        s.telemetry.loop_reset()
        s.telemetry.loop_lap("idle-wait")
        s.on_pod_add(_pod(0))
        assert s.schedule_pending().scheduled == 1
        assert spy.seen == [(None, None)]    # a callee pays one None check
        assert s.telemetry.recorder.records() == []
        assert s.telemetry._loop is None
        s.telemetry.loop_stage("start/wiring")       # a no-op, like the lap
        assert s.telemetry.loop_account() == {}
        assert s.telemetry._gc is None               # no collector hook

    def test_no_server_loop_no_loop_field(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        s.on_pod_add(_pod(0))
        s.schedule_pending()
        rec = s.telemetry.recorder.records()[-1]
        assert "loop" not in rec
        assert set(rec) >= {"children", "waits", "assumed_outstanding"}

    def test_record_fields_the_benchmark_reads_are_what_they_were(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        tick = {"n": 0}

        def tel_clock():
            tick["n"] += 1
            return tick["n"] * 0.001

        s.telemetry.clock = tel_clock
        for i in range(4):
            s.on_pod_add(_pod(i))
        s.schedule_pending()
        rec = s.telemetry.recorder.records()[-1]
        # one clock read opens the span, one closes each phase, one ends
        # the wave (and log_if_long reads one after the record is made):
        # nothing this issue added reads the telemetry's clock in a wave
        assert rec["t_start"] == 0.001
        assert [d for _, d in rec["phases"]] == [0.001] * 10
        assert rec["duration_s"] == 0.011
        assert tick["n"] == 13
        assert set(rec["device_split"]) == {"launch_s", "execute_s",
                                            "readback_s"}
        assert rec["snapshot_mode"] == "full"
        assert rec["stats"]["scheduled"] == 4


class TestWaits:
    def test_queue_wait_is_read_at_pop_and_keeps_the_stamp(self):
        from kubernetes_tpu.sched.metrics import POD_E2E_LATENCY

        clk = {"t": 0.0}
        s = _scheduler(clk)
        s.on_pod_add(_pod(0))
        clk["t"] = 1.0
        s.on_pod_add(_pod(1))
        s.on_pod_add(_pod(2))
        clk["t"] = 3.0
        before = POD_E2E_LATENCY.count()
        s.schedule_pending()
        rec = s.telemetry.recorder.records()[-1]
        assert rec["waits"]["queue"] == [3, 7.0, 3.0]
        # the commit still closed each pod's watch-to-bind span
        assert POD_E2E_LATENCY.count() == before + 3
        assert len(s.telemetry.tracker) == 0

    def test_tracker_waits_skips_unstamped_keys(self):
        tr = PodLatencyTracker()
        tr.stamp("a", 1.0)
        tr.stamp("b", 2.5)
        assert tr.waits(["a", "b", "never"], 4.0) == [2, 4.5, 3.0]
        assert tr.first_seen("a") == 1.0 and len(tr) == 2

    def test_confirm_lag_and_assumed_outstanding(self):
        from kubernetes_tpu.state.cache import SchedulerCache

        t = {"now": 100.0}
        cache = SchedulerCache(ttl=30.0)
        cache.lag_clock = lambda: t["now"]
        for n in make_nodes(2):
            cache.add_node(n)
        names = [n.name for n in cache.nodes()]
        pods = [_pod(i) for i in range(5)]
        for p in pods:
            cache.assume_pod(p, names[0])
        assert cache.drain_confirm_waits() == ([0, 0.0, 0.0], 5)
        for p in pods[:3]:
            cache.finish_binding(p.key, now=0.0)
        t["now"] = 100.5
        cache.add_pod(_pod(0, node_name=names[0]))      # confirmed
        t["now"] = 102.0
        cache.add_pod(_pod(1, node_name=names[1]))      # onto another node
        cache.forget_pod(pods[3].key)                   # bind failed
        cache.remove_pod(pods[4].key)                   # deleted while assumed
        assert cache.drain_confirm_waits() == ([2, 2.5, 2.0], 1)
        assert cache.cleanup(now=1000.0) == [pods[2].key]   # TTL expiry
        assert cache.drain_confirm_waits() == ([0, 0.0, 0.0], 0)
        assert cache.counts()[2] == 0
        cache.assume_pod(_pod(7), names[0])
        assert len(cache.forget_assumed()) == 1
        assert cache.drain_confirm_waits()[1] == 0

    def test_wave_record_carries_confirmations_since_the_last_wave(self):
        clk = {"t": 0.0}
        s = _scheduler(clk)
        lag = {"now": 10.0}
        s.cache.lag_clock = lambda: lag["now"]
        s.on_pod_add(_pod(0))
        s.on_pod_add(_pod(1))
        s.schedule_pending()
        first = s.telemetry.recorder.records()[-1]
        assert first["assumed_outstanding"] == 0
        assert first["waits"]["confirm"] == [0, 0.0, 0.0]
        lag["now"] = 10.25
        node = dict(s.binder.bound)["default/p0"]
        s.on_pod_add(_pod(0, node_name=node))           # the informer's echo
        s.on_pod_add(_pod(2))
        s.schedule_pending()
        rec = s.telemetry.recorder.records()[-1]
        assert rec["waits"]["confirm"] == [1, 0.25, 0.25]
        assert rec["assumed_outstanding"] == 1          # p1 still unconfirmed
        # the expiry pass looked at the one assumed pod and at no other
        assert (first["assumed_examined"], first["assumed_expired"]) == (0, 0)
        assert (rec["assumed_examined"], rec["assumed_expired"]) == (1, 0)
        clk["t"] = 1e6                  # p1's and p2's echoes never came
        s.on_pod_add(_pod(3))
        s.schedule_pending()
        late = s.telemetry.recorder.records()[-1]
        assert (late["assumed_examined"], late["assumed_expired"]) == (2, 2)
        assert late["assumed_outstanding"] == 0


class _ScriptedStop:
    """Stands in for SchedulerServer._stop: `wait(t)` advances the injected
    clock by t (nothing sleeps) and runs the next scripted action; the loop
    ends when the script does."""

    def __init__(self, clk, script):
        self.clk, self.script = clk, list(script)

    def is_set(self):
        return not self.script

    def wait(self, timeout=None):
        self.clk["t"] += timeout or 0.0
        if self.script:
            self.script.pop(0)()

    def set(self):
        self.script = []


def _loop_server(clk, batch_window=0.15):
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.sched.server import SchedulerServer

    s = _scheduler(clk)
    s.telemetry.clock = lambda: clk["t"]

    class _SlowBinder(RecordingBinder):
        def bind(self, pod, node_name):
            clk["t"] += 0.002          # a Binding takes time on this clock
            return super().bind(pod, node_name)

    s.binder = _SlowBinder()
    srv = SchedulerServer(Client.local(APIServer()), scheduler=s,
                          cycle_interval=0.02, batch_window=batch_window)
    return srv, s


class TestServerLoopSpans:
    def test_loop_phases_sum_to_the_gap_between_waves(self):
        clk = {"t": 50.0}
        srv, s = _loop_server(clk)

        def add(lo, hi):
            return lambda: [s.on_pod_add(_pod(i)) for i in range(lo, hi)]

        nothing = lambda: None
        # wave 1 starts at once (its pods were listed 1.5 s before the
        # peek: no batch-wait); three empty polls, pods for wave 2 arrive
        # on the fourth, its (short: < 32 pending) batch-wait, two polls
        srv._stop = _ScriptedStop(clk, [nothing, nothing, nothing,
                                        add(40, 45), nothing, nothing,
                                        nothing])
        for i in range(40):
            s.on_pod_add(_pod(i))
        s.telemetry.loop_reset()
        clk["t"] += 1.5                 # list + sync, before the loop runs
        srv._loop()
        w1, w2 = [r for r in s.telemetry.recorder.records()
                  if r["stats"]["attempted"]]
        assert [w1["stats"]["scheduled"], w2["stats"]["scheduled"]] == [40, 5]
        # wave 1: everything since the server started
        assert w1["loop"]["t_start"] == 50.0
        assert dict(map(tuple, w1["loop"]["phases"])) == pytest.approx(
            {"start": 1.5, "lock-wait": 0.0})
        assert (w1["gather_age_s"], w1["gather_wait_s"]) == (1.5, 0.0)
        assert sum(d for _, d in w1["loop"]["phases"]) == pytest.approx(
            w1["t_start"] - 50.0)
        # wave k+1: the gap since wave k ended, every second of it named
        gap = w2["t_start"] - (w1["t_start"] + w1["duration_s"])
        assert w2["loop"]["t_start"] == pytest.approx(
            w1["t_start"] + w1["duration_s"])
        phases = dict(map(tuple, w2["loop"]["phases"]))
        assert sum(phases.values()) == pytest.approx(gap, abs=1e-9)
        assert phases == pytest.approx(
            {"post-wave": 0.0, "lock-wait": 0.0, "idle-wait": 0.08,
             "batch-wait": 0.05})
        assert (w2["gather_age_s"], w2["gather_wait_s"]) == (0.0, 0.05)
        # the wave's own span is untouched by the loop's account
        assert w1["duration_s"] == pytest.approx(40 * 0.002)

    def test_handlers_are_counted_onto_the_next_wave(self):
        from kubernetes_tpu.models.workloads import make_nodes as mk

        clk = {"t": 0.0}
        srv, s = _loop_server(clk)
        s.telemetry.loop_reset()
        real = s.on_node_update

        def slow_update(node):
            clk["t"] += 0.004
            real(node)

        s.on_node_update = slow_update
        node = mk(1)[0]
        obj = {"metadata": {"name": node.name, "labels": dict(node.labels)},
               "status": {"allocatable": {"cpu": "4", "memory": "8Gi",
                                          "pods": "110"}}}
        for _ in range(3):
            srv._on_node_update(obj, obj)
        s.on_pod_add(_pod(0))
        srv.run_one_wave()
        rec = s.telemetry.recorder.records()[-1]
        assert rec["loop"]["handlers"] == {
            "calls": 3, "wait_s": 0.0, "held_s": pytest.approx(0.012)}
        s.on_pod_add(_pod(1))
        srv.run_one_wave()
        again = s.telemetry.recorder.records()[-1]
        assert again["loop"]["handlers"]["calls"] == 0   # drained, not kept

    def test_idle_record_leaves_the_loop_account_to_the_next_wave(self):
        clk = {"t": 0.0}
        srv, s = _loop_server(clk)
        s.telemetry.loop_reset()
        s.telemetry.loop_lap("start")
        s.telemetry.note_supervisor_event("rewarm", "x")
        srv.run_one_wave()                       # empty wave, event drained
        idle = s.telemetry.recorder.records()[-1]
        assert idle["engine"] == "idle" and "loop" not in idle
        clk["t"] += 0.5
        s.on_pod_add(_pod(0))
        srv.run_one_wave()
        rec = s.telemetry.recorder.records()[-1]
        assert sum(d for _, d in rec["loop"]["phases"]) == \
            pytest.approx(rec["t_start"])


    def test_start_stages_sum_to_the_start_lap(self, monkeypatch, caplog):
        """`SchedulerServer.start()` over scripted informers: each stretch
        of it is a stage below the `start` lap, an informer's own round
        grafted under the stage that waited for it; a start that goes on
        unsynced says so, on the record, in the log and in a counter."""
        from kubernetes_tpu.sched import server as server_mod
        from kubernetes_tpu.sched.metrics import START_UNSYNCED

        clk = {"t": 50.0}
        srv, s = _loop_server(clk)
        # resource: (seconds its sync took, synced in time, its round)
        script = {
            "nodes": (0.25, True, {"list": [1, 0.1, 0.1],
                                   "list/apiserver.list": [1, 0.08, 0.08],
                                   "index": [1, 0.01, 0.01],
                                   "handlers": [1, 0.125, 0.125],
                                   "handlers/decode": [8, 0.0625, 0.03]}),
            "pods": (1.25, False, None),
            # the four volume informers, listed before the nodes (PR 45)
            "csinodes": (0.125, True, {"list": [1, 0.1, 0.1]}),
            "storageclasses": (0.0, True, {}),
            "persistentvolumes": (0.0625, True, {}),
            "persistentvolumeclaims": (0.0625, True, {})}

        class _Informer:
            last_sync, relists, lister = None, 1, None

            def __init__(self, rc):
                self.rc = rc

            def add_handlers(self, **kw):
                pass

            def start(self):
                return self

            def stop(self):
                pass

            def buffered(self):
                return 0

            def wait_for_sync(self, timeout=10.0):
                cost, synced, children = script[self.rc.resource]
                clk["t"] += cost
                if synced:
                    gc.collect()   # a pause inside this stage
                if synced:
                    self.last_sync = {"synced": True, "children": children}
                return synced

        monkeypatch.setattr(server_mod, "SharedInformer", _Informer)
        nothing = lambda: None
        srv._stop = _ScriptedStop(clk, [nothing, nothing])
        for i in range(40):
            s.on_pod_add(_pod(i))
        unsynced = START_UNSYNCED.value(component="scheduler",
                                        resource="pods")
        with caplog.at_level(logging.WARNING,
                             logger="kubernetes_tpu.sched.server"):
            srv.start()
        srv._threads[0].join(30)
        assert not srv._threads[0].is_alive()
        w1 = next(r for r in s.telemetry.recorder.records()
                  if r["stats"]["attempted"])
        loop = w1["loop"]
        phases = dict(map(tuple, loop["phases"]))
        # the top level is what it was: laps, contiguous from t_start (no
        # batch-wait: the pods were there before the start's 1.75 s)
        assert set(phases) == {"start", "lock-wait"}
        assert sum(phases.values()) == pytest.approx(w1["t_start"] - 50.0)
        stages = {p: v[1] for p, v in loop["children"].items()
                  if p.count("/") == 1}
        assert stages == pytest.approx({"start/volumes-sync": 0.25,
                                        "start/nodes-sync": 0.25,
                                        "start/pods-sync": 1.25,
                                        "start/wiring": 0.0})
        assert sum(stages.values()) == pytest.approx(phases["start"])
        # the volume stage holds its four lists, each a stage below it
        assert {p: v[1] for p, v in loop["children"].items()
                if p.startswith("start/volumes-sync/")
                and p.count("/") == 2} == pytest.approx({
            "start/volumes-sync/csinodes": 0.125,
            "start/volumes-sync/storageclasses": 0.0,
            "start/volumes-sync/persistentvolumes": 0.0625,
            "start/volumes-sync/persistentvolumeclaims": 0.0625})
        assert loop["children"]["start/volumes-sync/csinodes/list"] == [
            1, 0.1, 0.1]
        # the nodes' round, grafted under the stage that waited for it;
        # the pods' had not ended: nothing of it, and the verdict kept
        assert loop["children"]["start/nodes-sync/handlers/decode"] == [
            8, 0.0625, 0.03]
        assert loop["children"]["start/nodes-sync/list/apiserver.list"] \
            == [1, 0.08, 0.08]
        assert not [p for p in loop["children"]
                    if p.startswith("start/pods-sync/") and "/gc" not in p]
        # the collection that ran inside the nodes' stage, below it: a
        # pause the spans beside it hold, in the children's own form
        every, full = (loop["children"]["start/nodes-sync/gc" + sub]
                       for sub in ("", "/full"))
        assert every[0] >= full[0] >= 1 and every[1] >= full[1] >= full[2] > 0
        assert "gc" not in loop
        assert loop["synced"] == {
            "start/nodes-sync": True, "start/pods-sync": False,
            **{f"start/volumes-sync/{r}": True for r in (
                "csinodes", "storageclasses", "persistentvolumes",
                "persistentvolumeclaims")}}
        assert START_UNSYNCED.value(component="scheduler",
                                    resource="pods") == unsynced + 1
        assert any("pods informer had not synced" in r.getMessage()
                   for r in caplog.records)

    def test_gc_fields_cover_the_collections_between_two_records(self):
        import time

        clk = {"t": 0.0}
        s = _scheduler(clk)
        gc.collect()
        gc.disable()   # only the collections this test forces
        try:
            s.on_pod_add(_pod(0))
            s.schedule_pending()
            t0 = time.perf_counter()
            gc.collect()
            gc.collect(1)
            gc.collect()
            t1 = time.perf_counter()
            s.on_pod_add(_pod(1))
            s.schedule_pending()
            s.on_pod_add(_pod(2))
            s.schedule_pending()
        finally:
            gc.enable()
        _first, forced, quiet = s.telemetry.recorder.records()
        assert forced["gc_full_collections"] == 2
        assert 0 < forced["gc_max_pause_s"] <= forced["gc_pause_s"] < t1 - t0
        assert t0 <= forced["gc_max_pause_at"] <= t1
        assert {k: v for k, v in quiet.items() if k.startswith("gc_")} == {
            "gc_full_collections": 0, "gc_pause_s": 0.0,
            "gc_max_pause_s": 0.0, "gc_max_pause_at": None}

    def test_gc_account_and_its_series(self):
        from kubernetes_tpu.component.metrics import DEFAULT_REGISTRY
        from kubernetes_tpu.sched.telemetry import (
            GcAccount,
            _GcSeries,
            gc_account,
        )

        # the process's account: one hook, however many recorders
        acct = gc_account()
        SchedulerTelemetry(name="another")
        assert gc.callbacks.count(acct) == 1 and gc_account() is acct
        gc.disable()
        try:
            mark = acct.mark()
            gc.collect(0)
            gc.collect()
        finally:
            gc.enable()
        assert acct.since(mark)["gc_full_collections"] == 1
        text = DEFAULT_REGISTRY.expose_text()
        for gen, n in enumerate(acct.collections):
            assert (f'process_gc_collections_total{{generation="{gen}"}} '
                    f'{float(n)}') in text
        assert 'process_gc_pause_seconds_total{generation="2"}' in text
        # the longest pause since an instant: the first kept one that
        # ENDED after it
        ticks = iter((0.5, 1.0, 1.9, 2.0, 2.7, 3.0, 3.8, 4.0, 5.0))
        a = GcAccount(clock=lambda: next(ticks))
        for gen in (2, 0, 1, 0):     # pauses of 0.5, 0.1, 0.3, 0.2 s
            a("start", {"generation": gen})
            a("stop", {"generation": gen})
        assert a.collections == [2, 1, 1]
        assert [round(dt, 6) for _t, dt in a.all._peaks] == [0.5, 0.3, 0.2]
        assert a.since(((1, 0.5), (1, 0.5), 1.5)) == {
            "gc_full_collections": 0, "gc_pause_s": 0.6,
            "gc_max_pause_s": 0.3, "gc_max_pause_at": 2.7}
        # a collection that straddles the mark (2.7 to 3.0) is counted
        # where it stops: its pause AND its peak are this interval's
        assert a.since(((2, 0.6), (1, 0.5), 2.9)) == {
            "gc_full_collections": 0, "gc_pause_s": 0.5,
            "gc_max_pause_s": 0.3, "gc_max_pause_at": 2.7}
        assert a.since(((3, 0.9), (1, 0.5), 3.5))["gc_max_pause_at"] == 3.8
        # the same in a Trace's form, for the stage it is grafted below
        assert a.children(((0, 0.0), (0, 0.0), 0.0)) == {
            "gc": [4, pytest.approx(1.1), 0.5], "gc/full": [1, 0.5, 0.5]}
        assert a.children(((1, 0.5), (1, 0.5), 1.5)) == {
            "gc": [3, pytest.approx(0.6), pytest.approx(0.3)]}
        mark = a.mark()
        assert mark == ((4, pytest.approx(1.1)), (1, 0.5), 5.0)
        assert a.children(mark) == {}
        assert a.since(mark)["gc_max_pause_at"] is None
        # a series IS the account's list, whichever way it is read
        series = _GcSeries("gc_test_total", "", a.collections)
        assert series.value(generation="0") == 2.0 and series.total() == 4.0
        assert series.expose()[-3:] == [
            f'gc_test_total{{generation="{g}"}} {n}'
            for g, n in enumerate((2.0, 1.0, 1.0))]


def _event_server(clk, write_s=0.0):
    """`_loop_server` whose FailedScheduling Events can be watched: a lister
    that knows every pod, and Event creates that wait at `gate` and then
    take `write_s` seconds (off the interpreter, as the store's calls do)."""
    import time
    import types

    srv, s = _loop_server(clk)
    srv.pod_informer = types.SimpleNamespace(
        stop=lambda: None, relists=0, lister=types.SimpleNamespace(
            get=lambda ns, name: {"kind": "Pod", "metadata": {
                "name": name, "namespace": ns, "uid": f"uid-{name}"}}))
    gate = threading.Event()
    create = srv.client.events.create

    def held(obj, ns=None):
        assert gate.wait(10)
        time.sleep(write_s)
        return create(obj, ns)

    srv.client.events.create = held

    def failed_events():
        return [e for e in srv.client.events.list("default")["items"]
                if e["reason"] == "FailedScheduling"]

    return srv, s, gate, failed_events


def _nofit(i):
    return Pod(name=f"nofit{i}", creation_index=i,
               requests=Resources.make(cpu="4096", memory="8Mi"))


class _HeldByAWave:
    """Stands in for SchedulerServer._mu while a wave holds it until the
    injected clock reads `free_at`: a handler that asks for it earlier
    waits, which on this clock is a jump to `free_at`."""

    def __init__(self, clk, free_at):
        self.clk, self.free_at = clk, free_at
        self._lock = threading.Lock()

    def acquire(self, *a):
        self.clk["t"] = max(self.clk["t"], self.free_at)
        return self._lock.acquire(*a)

    def release(self):
        self._lock.release()

    __enter__ = acquire

    def __exit__(self, *exc):
        self.release()


class _LoopFirst:
    """Stands in for SchedulerServer._mu as an unfair lock at its worst:
    a handler thread gets it only once the loop's thread (the one that
    made this) has had it and let it go."""

    def __init__(self):
        self._lock = threading.Lock()
        self._loop = threading.get_ident()
        self._gate = threading.Event()

    def acquire(self, *a):
        if threading.get_ident() != self._loop:
            self._gate.wait(30)
        return self._lock.acquire(*a)

    def release(self):
        self._lock.release()
        if threading.get_ident() == self._loop:
            self._gate.set()

    __enter__ = acquire

    def __exit__(self, *exc):
        self.release()


def _no_wait(timeout=None):
    raise AssertionError(f"the loop slept {timeout}")


class TestGatheringWait:
    """ISSUE 47: the window pods are given to gather into a wave counts
    from when the oldest pod in the active queue reached the scheduler
    (its handler's entry, before the wait for `_mu`), not from the loop's
    peek (sched/server.py `_gather`)."""

    def _beat(self, srv, s):
        """One turn of the loop: decide when the wave starts, run it;
        the record of the wave."""
        srv._gather(s.telemetry.loop_lap)
        assert srv.run_one_wave() is not None, srv.last_wave_error
        return s.telemetry.recorder.records()[-1]

    @pytest.mark.parametrize("waited,slept", [
        (0.0, 0.05), (0.02, 0.03), (0.05, 0.0), (0.06, 0.0), (1.5, 0.0)])
    def test_the_window_counts_from_the_handlers_entry(self, waited, slept):
        """A pod whose handler entered `waited` s before the peek (it
        waited out a wave behind `_mu`) is held for what is left of the
        0.05 s window, and not at all once the window has passed: no
        sleep, no `batch-wait` lap; the record says which."""
        from kubernetes_tpu.api.v1 import pod_to_v1

        clk = {"t": 0.0}
        srv, s = _loop_server(clk)
        s.telemetry.loop_reset()
        srv._mu = _HeldByAWave(clk, free_at=waited)
        srv._on_pod_add(pod_to_v1(_pod(0)))   # enters at 0.0, waits
        assert clk["t"] == waited
        srv._mu = threading.Lock()
        naps = []
        srv._stop = _ScriptedStop(clk, [lambda: naps.append(clk["t"])])
        if not slept:
            srv._stop.wait = _no_wait
        rec = self._beat(srv, s)
        assert rec["stats"]["scheduled"] == 1
        assert rec["gather_age_s"] == pytest.approx(waited)
        assert rec["gather_wait_s"] == pytest.approx(slept)
        phases = dict(map(tuple, rec["loop"]["phases"]))
        assert phases.get("batch-wait", 0.0) == pytest.approx(slept)
        assert ("batch-wait" in phases) == bool(slept)
        assert naps == ([pytest.approx(0.05)] if slept else [])

    def test_the_first_pod_at_an_idle_loop_is_held_a_window(self):
        """...and a pod created within the window of it rides its wave;
        one created after the wave's pop waits for the next."""
        clk = {"t": 100.0}
        srv, s = _loop_server(clk)
        s.telemetry.loop_reset()
        s.on_pod_add(_pod(0))
        # 0.03 s into the 0.05 s the loop sleeps, a second pod arrives
        srv._stop = _ScriptedStop(clk, [
            lambda: s.on_pod_add(_pod(1), arrived=clk["t"] - 0.02)])
        rec = self._beat(srv, s)
        assert rec["stats"]["scheduled"] == 2
        assert (rec["gather_age_s"], rec["gather_wait_s"]) == (0.0, 0.05)
        s.on_pod_add(_pod(2))
        srv._stop = _ScriptedStop(clk, [lambda: None])
        again = self._beat(srv, s)
        assert again["stats"]["scheduled"] == 1
        assert again["gather_wait_s"] == pytest.approx(0.05)

    def test_storms_keep_the_full_window_from_their_oldest_pod(self):
        """32 or more pending: `batch_window` (0.15 s), counted the same
        way."""
        clk = {"t": 100.0}
        srv, s = _loop_server(clk)
        s.telemetry.loop_reset()
        for i in range(40):
            s.on_pod_add(_pod(i), arrived=99.9 + i * 0.001)
        srv._stop = _ScriptedStop(clk, [lambda: None])
        rec = self._beat(srv, s)
        assert rec["stats"]["scheduled"] == 40
        assert rec["gather_age_s"] == pytest.approx(0.1)
        assert rec["gather_wait_s"] == pytest.approx(0.05)

    def test_a_requeued_pod_counts_from_its_requeue(self):
        """`add_prompt_retry` (a preemptor whose victims were just
        evicted): the wait before the wave of requeued pods stays what it
        was, whatever the pod's first-seen stamp says."""
        clk = {"t": 100.0}
        srv, s = _loop_server(clk)
        s.telemetry.loop_reset()
        s.on_pod_add(_pod(0))
        (pod, attempts), = s.queue.pop_batch(8, now=100.0)
        clk["t"] = 100.5
        s.queue.add_prompt_retry(pod, attempts, now=clk["t"])
        clk["t"] = 100.51
        srv._stop = _ScriptedStop(clk, [lambda: None])
        rec = self._beat(srv, s)
        assert rec["stats"]["scheduled"] == 1
        assert rec["gather_age_s"] == pytest.approx(0.01)
        assert rec["gather_wait_s"] == pytest.approx(0.04)
        # the pod's own wait still counts from when it was first seen
        assert rec["waits"]["queue"] == [1, pytest.approx(0.55),
                                         pytest.approx(0.55)]

    @pytest.mark.parametrize("telemetry", ["1", "0"])
    def test_first_seen_is_the_handlers_entry(self, monkeypatch, telemetry):
        """The queue entry's `timestamp` and the latency tracker's stamp
        are the instant the handler was entered, not the instant it got
        `_mu`; with telemetry off the handler is not timed and the entry
        is stamped the same."""
        from kubernetes_tpu.api.v1 import pod_to_v1

        monkeypatch.setenv("KTPU_TELEMETRY", telemetry)
        clk = {"t": 100.0}
        srv, s = _loop_server(clk)
        assert s.telemetry.enabled == (telemetry == "1")
        srv._mu = _HeldByAWave(clk, free_at=100.045)
        srv._on_pod_add(pod_to_v1(_pod(0)))
        assert clk["t"] == 100.045
        key = _pod(0).key
        assert s.queue._active_keys[key].timestamp == 100.0
        assert s.queue.active_stats() == (1, 100.0)
        if s.telemetry.enabled:
            assert s.telemetry.tracker.first_seen(key) == 100.0
        else:
            assert s.queue.tracker is None
        # an update that admits the pod again: the same rule
        clk["t"] = 101.0
        srv._mu = _HeldByAWave(clk, free_at=101.03)
        obj = pod_to_v1(_pod(0))
        srv._on_pod_update(obj, obj)
        assert s.queue._active_keys[key].timestamp == 101.0
        assert not srv._handlers_waiting

    def test_arrival_is_less_the_events_wait_in_the_informers_buffer(self):
        """One informer thread delivers the pod events in turn: an event
        behind the one whose handler waits out a wave reaches its own
        handler only after it. What the informer says the event waited in
        its buffer comes off the handler's entry."""
        import types

        from kubernetes_tpu.api.v1 import pod_to_v1

        clk = {"t": 100.0}
        srv, s = _loop_server(clk)
        srv.pod_informer = types.SimpleNamespace(
            delivery_lag=lambda: 0.04, buffered=lambda: 0, relists=0)
        srv._on_pod_add(pod_to_v1(_pod(0)))
        assert s.queue.active_stats() == (1, pytest.approx(99.96))
        assert s.telemetry.tracker.first_seen(_pod(0).key) == \
            pytest.approx(99.96)

    def test_a_handler_at_the_lock_goes_before_the_pop(self):
        """`_mu` is not fair: after a wave the loop can win it back from a
        handler that has stood at it since before the wave ended. The loop
        sees the handler and lets it through before it decides."""
        from kubernetes_tpu.api.v1 import pod_to_v1

        clk = {"t": 100.0}
        srv, s = _loop_server(clk)
        s.telemetry.loop_reset()
        srv._mu = _LoopFirst()
        handler = threading.Thread(
            target=srv._on_pod_add, args=(pod_to_v1(_pod(0)),))
        handler.start()
        for _ in range(3000):
            if srv._handlers_waiting:
                break
            threading.Event().wait(0.01)
        assert srv._handlers_waiting and s.queue.active_stats()[0] == 0
        srv._stop = _ScriptedStop(clk, [lambda: None])
        rec = self._beat(srv, s)
        handler.join(30)
        assert rec["stats"]["scheduled"] == 1    # not an empty wave
        assert not srv._handlers_waiting
        assert "lock-wait" in dict(map(tuple, rec["loop"]["phases"]))

    def test_events_in_the_informers_buffer_go_before_the_pop(self):
        """What waits in the pod informer's buffer reached the scheduler
        before the peek too: the loop waits for the informer's thread to
        deliver it, at most a window."""
        import types

        clk = {"t": 100.0}
        srv, s = _loop_server(clk)
        s.telemetry.loop_reset()
        left = [3]

        class _Through:            # the informer delivers one per wait
            def clear(self):
                pass

            def wait(self, timeout):
                clk["t"] += 0.001
                s.on_pod_add(_pod(left[0]), arrived=99.99)
                left[0] -= 1

        srv._handlers_through = _Through()
        srv.pod_informer = types.SimpleNamespace(
            delivery_lag=lambda: 0.0, buffered=lambda: left[0], relists=0)
        srv._stop = _ScriptedStop(clk, [lambda: None])
        rec = self._beat(srv, s)
        assert rec["stats"]["scheduled"] == 3
        assert rec["gather_age_s"] == pytest.approx(0.013)
        assert rec["gather_wait_s"] == pytest.approx(0.037)
        # a buffer that never empties holds the loop for a window, no more
        left[0] = 10 ** 6
        srv._handlers_through.wait = lambda timeout: clk.__setitem__(
            "t", clk["t"] + 0.01)
        srv._stop = _ScriptedStop(clk, [lambda: None])
        t0 = clk["t"]
        srv._gather(s.telemetry.loop_lap)
        assert clk["t"] - t0 == pytest.approx(0.05)


    def test_handlers_and_the_loop_under_a_short_switch_interval(self):
        """More handler threads than cores against the loop's turns, the
        interpreter switching every 10 us: every pod is bound once, no
        handler is left standing in `waiting`, nothing hangs."""
        import sys
        import time

        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.api.v1 import pod_to_v1
        from kubernetes_tpu.client import Client
        from kubernetes_tpu.sched.server import SchedulerServer

        binder = RecordingBinder()
        s = Scheduler(binder=binder, batch_size=64)
        for n in make_nodes(8):
            s.on_node_add(n)
        srv = SchedulerServer(Client.local(APIServer()), scheduler=s,
                              cycle_interval=0.001, batch_window=0.004)
        threads, each = 16, 12
        objs = [[pod_to_v1(_pod(t * each + i)) for i in range(each)]
                for t in range(threads)]

        def feed(mine):
            for o in mine:
                srv._on_pod_add(o)

        workers = [threading.Thread(target=feed, args=(m,)) for m in objs]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for w in workers:
                w.start()
            deadline = time.monotonic() + 120
            while len(binder.bound) < threads * each \
                    and time.monotonic() < deadline:
                srv._gather(s.telemetry.loop_lap)
                assert srv.run_one_wave() is not None, srv.last_wave_error
            for w in workers:
                w.join(30)
        finally:
            sys.setswitchinterval(interval)
        assert not any(w.is_alive() for w in workers)
        assert sorted(k for k, _ in binder.bound) == sorted(
            _pod(i).key for i in range(threads * each))
        assert not srv._handlers_waiting


class TestServerEventsLeaveTheLoop:
    def test_run_one_wave_only_queues_its_failed_scheduling_events(self):
        from kubernetes_tpu.sched.metrics import FAILED_EVENTS

        k = 12
        before = {o: FAILED_EVENTS.value(outcome=o)
                  for o in ("queued", "emitted", "dropped")}
        srv, s, gate, failed_events = _event_server({"t": 0.0})
        s.telemetry.loop_reset()
        for i in range(k):
            s.on_pod_add(_nofit(i))
        stats = srv.run_one_wave()
        assert stats.unschedulable == k and len(stats.failed_keys) == k
        # the wave is over and not one Event is at the apiserver
        assert failed_events() == [] and srv.recorder.pending() == k
        assert s.telemetry.recorder.records()[-1]["events_pending"] == 0
        s.on_pod_add(_pod(100))
        srv.run_one_wave()          # the next wave pops over the backlog
        rec = s.telemetry.recorder.records()[-1]
        assert rec["stats"]["scheduled"] == 1 and rec["events_pending"] == k
        gate.set()
        assert srv.recorder.flush(timeout=10)
        assert sorted(e["involvedObject"]["name"] for e in failed_events()) \
            == sorted(f"nofit{i}" for i in range(k))
        assert all(e["involvedObject"]["uid"] == "uid-" +
                   e["involvedObject"]["name"] and e["count"] == 1
                   and e["message"] == "no nodes available to schedule pod"
                   for e in failed_events())
        after = {o: FAILED_EVENTS.value(outcome=o) for o in before}
        assert {o: after[o] - before[o] for o in before} == {
            "queued": k, "emitted": k, "dropped": 0}
        srv.stop()

    @pytest.mark.parametrize("how, landed", [("stop", "all"),
                                             ("crash", "few")])
    def test_stop_flushes_the_queue_and_crash_abandons_it(self, how, landed):
        k = 20
        srv, s, gate, failed_events = _event_server({"t": 0.0},
                                                    write_s=0.005)
        gate.set()
        for i in range(k):
            s.on_pod_add(_nofit(i))
        srv.run_one_wave()
        getattr(srv, how)()
        assert srv.recorder.pending() == 0
        # 5 ms a write: stop() waited for all 20; crash() came back while
        # at most the one in flight and its successor could have landed
        n = len(failed_events())
        assert n == k if landed == "all" else n <= 2

    def test_wave_two_schedules_against_unconfirmed_assumes(self):
        """Back-to-back waves, as the drain now runs them: wave 2's pods
        need wave 1's (in-zone affinity, host anti-affinity) before the
        informer has confirmed one Binding of wave 1."""
        from kubernetes_tpu.api import semantics as sem
        from kubernetes_tpu.api.types import (Affinity, LabelSelector,
                                              PodAffinityTerm)
        from kubernetes_tpu.models.workloads import HOSTNAME, ZONE

        srv, s = _loop_server({"t": 0.0})
        for n in make_nodes(24, zones=8)[8:]:   # three hosts to a zone
            s.on_node_add(n)
        nodes = {n.name: n for n in s.cache.nodes()}
        web = LabelSelector.of(match_labels={"app": "web"})
        on_host = (PodAffinityTerm(selector=web, topology_key=HOSTNAME),)
        in_zone = (PodAffinityTerm(selector=web, topology_key=ZONE),)
        small = Resources.make(cpu="10m", memory="8Mi")
        wave1 = [Pod(name=f"web{i}", labels={"app": "web"}, requests=small,
                     affinity=Affinity(anti_required=on_host),
                     creation_index=i) for i in range(2)]
        wave2 = [Pod(name=f"cache{i}", labels={"app": "cache"},
                     requests=small, creation_index=10 + i,
                     affinity=Affinity(pod_required=in_zone,
                                       anti_required=on_host))
                 for i in range(3)]
        s.telemetry.loop_reset()
        for p in wave1:
            s.on_pod_add(p)
        assert srv.run_one_wave().scheduled == 2
        for p in wave2:             # no confirmation in between
            s.on_pod_add(p)
        assert srv.run_one_wave().scheduled == 3
        rec = s.telemetry.recorder.records()[-1]
        assert rec["assumed_outstanding"] == 2
        where = dict(s.binder.bound)
        existing = [dataclasses.replace(p, node_name=where[p.key])
                    for p in wave1]
        assert len({where[p.key] for p in wave1}) == 2
        for p in wave2:
            assert sem.interpod_affinity_fits(p, nodes[where[p.key]], nodes,
                                              existing)
            # and it had to use them: with no web pod in sight it fits
            # nowhere
            assert not any(sem.interpod_affinity_fits(p, n, nodes, [])
                           for n in nodes.values())


class TestRequestAndTxnMetrics:
    def test_observe_at_is_observe_with_the_key_in_hand(self):
        h = Histogram("at_test", "", label_names=("verb", "resource"))
        h.observe(0.003, verb="create", resource="pods")
        h.observe_at(("create", "pods"), 0.004)
        assert h.count(verb="create", resource="pods") == 2
        assert h.sum_value(verb="create", resource="pods") == \
            pytest.approx(0.007)
        assert h.quantile(0.99, verb="create", resource="pods") == 0.005

    def test_create_and_bind_feed_the_three_families_and_the_trace(self):
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.apiserver.server import REQUEST_DURATION
        from kubernetes_tpu.client import Client
        from kubernetes_tpu.sched.metrics import BINDING_DURATION
        from kubernetes_tpu.storage.store import TXN_DURATION

        client = Client.local(APIServer())
        client.nodes.create({"apiVersion": "v1", "kind": "Node",
                             "metadata": {"name": "n0"}})
        n_create = REQUEST_DURATION.count(verb="create", resource="pods",
                                          subresource="")
        n_bind = REQUEST_DURATION.count(verb="create", resource="pods",
                                        subresource="binding")
        n_txn_c = TXN_DURATION.count(op="create")
        n_txn_u = TXN_DURATION.count(op="update")
        n_txn_l = TXN_DURATION.count(op="list")
        tr = Trace("op", clock=lambda: 0.0)
        token = ktrace.activate(tr)
        try:
            for i in range(3):
                client.pods.create({
                    "apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": f"p{i}", "namespace": "default"},
                    "spec": {"containers": [{"name": "c", "image": "x"}]}})
            tr.step("create")
            for i in range(3):
                client.pods.bind(f"p{i}", "n0", "default")
            tr.step("bind")
        finally:
            ktrace.deactivate(token)
        assert REQUEST_DURATION.count(verb="create", resource="pods",
                                      subresource="") == n_create + 3
        assert REQUEST_DURATION.count(verb="create", resource="pods",
                                      subresource="binding") == n_bind + 3
        assert TXN_DURATION.count(op="create") == n_txn_c + 3
        assert TXN_DURATION.count(op="update") == n_txn_u + 3
        ch = tr.children()
        # a create's admission reads its namespace's LimitRanges (twice),
        # ResourceQuotas and the PriorityClasses: four lists (ISSUE 37)
        assert sorted(ch) == [
            "bind/apiserver.bind", "bind/apiserver.bind/store.txn",
            "bind/apiserver.bind/store.txn/kv", "create/apiserver.create",
            "create/apiserver.create/store.list",
            "create/apiserver.create/store.list/kv",
            "create/apiserver.create/store.txn",
            "create/apiserver.create/store.txn/kv"]
        assert ch["create/apiserver.create/store.list"][0] == 12
        assert TXN_DURATION.count(op="list") == n_txn_l + 12
        for outer in ("bind/apiserver.bind", "create/apiserver.create"):
            assert ch[outer][0] == 3
            assert ch[outer + "/store.txn/kv"][1] \
                <= ch[outer + "/store.txn"][1] <= ch[outer][1]
        # a refused request is one observation too, and closes its span
        with pytest.raises(Exception):
            client.pods.create({"apiVersion": "v1", "kind": "Pod",
                                "metadata": {"name": "p0",
                                             "namespace": "default"},
                                "spec": {"containers": [
                                    {"name": "c", "image": "x"}]}})
        assert REQUEST_DURATION.count(verb="create", resource="pods",
                                      subresource="") == n_create + 4
        # scheduler_binding_duration_seconds: one sample per Binding written
        clk = {"t": 0.0}
        s = _scheduler(clk)
        before = BINDING_DURATION.count()
        for i in range(6):
            s.on_pod_add(_pod(i))
        assert s.schedule_pending().scheduled == 6
        assert BINDING_DURATION.count() == before + 6
