"""Decision provenance (ISSUE 10; ops/assign.py explain_assignments +
sched/explain.py + the /debug/why surface; docs/OBSERVABILITY.md §Decision
provenance).

Covers: on-device attribution correctness (per-predicate counts reconcile
with the final mask), pod-vs-class granularity bit-equality (the runs
engine's once-per-class fan-out against the per-pod spec), KTPU_EXPLAIN
placement bit-equality across all three engines, kube-style rendering +
EventCorrelator-style dedupe, FailedScheduling events through a real
apiserver with the TTL-bounded events store, the why-pending debug
endpoint, the degraded-wave flight-recorder reconstruction drill, the
KTPU_FLIGHT_RING satellite, the docs metric-catalogue drift gate, and the
bench trend tool.
"""

import json
import os
import re
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubernetes_tpu.api.types import Pod, Resources
from kubernetes_tpu.models.workloads import make_nodes
from kubernetes_tpu.ops.assign import (
    EXPLAIN_PREDICATES,
    EXPLAIN_SCORE_COMPONENTS,
    explain_assignments,
    assign_batch,
    initial_state,
)
from kubernetes_tpu.ops.lattice import build_cycle, default_engine_config
from kubernetes_tpu.sched.cycle import (
    UNSCHEDULABLE_TAINT_KEY,
    _schedule_batch,
)
from kubernetes_tpu.sched.explain import (
    APIEventSink,
    DecisionExplainer,
    ReasonCorrelator,
    build_explainer,
    reason_fingerprint,
    render_unschedulable,
)
from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler
from kubernetes_tpu.state.encode import Encoder
from kubernetes_tpu.utils import faultline

pytestmark = pytest.mark.explain


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faultline.uninstall()


def _nodes(n=5, cpu="2"):
    return [Node_(f"n{i}", cpu) for i in range(n)]


def Node_(name, cpu="2"):
    from kubernetes_tpu.api.types import Node

    return Node(name=name,
                allocatable=Resources.make(cpu=cpu, memory="4Gi", pods=110))


def _pod(i, cpu="100m", **kw):
    return Pod(name=f"p{i}", requests=Resources.make(cpu=cpu, memory="16Mi"),
               creation_index=i, **kw)


def _encode(nodes, pods, existing=()):
    enc = Encoder()
    enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
    enc.vocabs.label_vals.intern("")
    tables, ex, pe, d = enc.encode_cluster(nodes, list(existing), pods, None)
    uk = jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY))
    ev = jnp.int32(enc.vocabs.label_vals.get(""))
    return (jax.device_put(tables), jax.device_put(ex), jax.device_put(pe),
            d, (uk, ev))


def _scheduler(monkeypatch, explain=True, batch_size=64, n_nodes=8,
               clk=None):
    monkeypatch.setenv("KTPU_EXPLAIN", "1" if explain else "0")
    kw = {}
    if clk is not None:
        kw["clock"] = lambda: clk["t"]
    s = Scheduler(binder=RecordingBinder(), batch_size=batch_size, **kw)
    s.prewarmer.enabled = False
    for n in make_nodes(n_nodes):
        s.on_node_add(n)
    return s


# --------------------------------------------------------------------- #
# on-device attribution correctness
# --------------------------------------------------------------------- #

class TestDeviceAttribution:
    def test_counts_reconcile_with_final_mask(self):
        nodes = _nodes(5)
        pods = [_pod(i) for i in range(3)] + [_pod(9, cpu="64")]
        tables, ex, pe, d, keys = _encode(nodes, pods)
        res, exp = _schedule_batch(tables, pe, keys, d.D, ex,
                                   has_node_name=d.has_node_name,
                                   explain=True)
        exp = jax.device_get(exp)
        node = np.asarray(res.node)
        for i in range(len(pods)):
            # the load-bearing identity: rejected_by_any == N - feasible
            assert exp.rejected_any[i] == \
                exp.valid_nodes[i] - exp.feasible_nodes[i]
            # every per-predicate count is bounded by the union, and the
            # union by the sum (counts overlap kube-style)
            assert exp.reasons[i].max(initial=0) <= exp.rejected_any[i]
            assert exp.rejected_any[i] <= exp.reasons[i].sum()
        # the huge pod fails fit on EVERY valid node and nothing else
        hi = 3
        assert node[hi] == -1
        r = dict(zip(EXPLAIN_PREDICATES, exp.reasons[hi]))
        assert r["fit"] == exp.valid_nodes[hi] == 5
        assert exp.feasible_nodes[hi] == 0
        assert sum(v for k, v in r.items() if k != "fit") == 0
        # a scheduled pod reports its chosen node and a score breakdown
        assert node[0] >= 0 and exp.part_node[0] == node[0]
        assert exp.score_parts[0].sum() > 0

    def test_pinned_pod_host_attribution(self):
        nodes = _nodes(4)
        # pinned to a node name that exists: host plane rejects the other 3
        pods = [_pod(0), Pod(name="pin", node_name="n2",
                             requests=Resources.make(cpu="100m",
                                                     memory="16Mi"),
                             creation_index=1)]
        tables, ex, pe, d, keys = _encode(nodes, pods)
        res, exp = _schedule_batch(tables, pe, keys, d.D, ex,
                                   has_node_name=d.has_node_name,
                                   explain=True)
        exp = jax.device_get(exp)
        r = dict(zip(EXPLAIN_PREDICATES, exp.reasons[1]))
        assert r["host"] == 3
        assert exp.feasible_nodes[1] == 1

    def test_pod_vs_class_granularity_bit_equal(self):
        nodes = _nodes(6)
        pods = ([_pod(i) for i in range(4)] + [_pod(8, cpu="64")]
                + [Pod(name="pin", node_name="n1",
                       requests=Resources.make(cpu="100m", memory="16Mi"),
                       creation_index=9)])
        tables, ex, pe, d, (uk, ev) = _encode(nodes, pods)
        cyc = build_cycle(tables, ex, uk, ev, d.D, 1.0,
                          default_engine_config())
        init = initial_state(tables, cyc)
        res = assign_batch(tables, cyc, pe, init)
        e_pod = jax.device_get(explain_assignments(tables, cyc, pe, res,
                                                   "pod"))
        e_cls = jax.device_get(explain_assignments(tables, cyc, pe, res,
                                                   "class"))
        for name in e_pod._fields:
            a, b = getattr(e_pod, name), getattr(e_cls, name)
            assert np.array_equal(np.asarray(a), np.asarray(b)), name

    def test_engines_attribution_agrees(self):
        nodes = _nodes(6)
        pods = [_pod(i) for i in range(5)] + [_pod(9, cpu="64")]
        outs = {}
        for engine in ("scan", "waves"):
            tables, ex, pe, d, keys = _encode(nodes, pods)
            res, exp = _schedule_batch(tables, pe, keys, d.D, ex,
                                       explain=True, engine=engine)
            outs[engine] = (np.asarray(res.node), jax.device_get(exp))
        assert np.array_equal(outs["scan"][0], outs["waves"][0])
        for name in outs["scan"][1]._fields:
            a = np.asarray(getattr(outs["scan"][1], name))
            b = np.asarray(getattr(outs["waves"][1], name))
            assert np.array_equal(a, b), name

    def test_explain_off_placement_bit_equality_all_engines(self):
        nodes = _nodes(6)
        pods = [_pod(i) for i in range(8)] + [_pod(20, cpu="64")]
        for engine in ("scan", "waves"):
            tables, ex, pe, d, keys = _encode(nodes, pods)
            plain = _schedule_batch(tables, pe, keys, d.D, ex,
                                    engine=engine)
            res, _exp = _schedule_batch(tables, pe, keys, d.D, ex,
                                        explain=True, engine=engine)
            assert np.array_equal(np.asarray(plain.node),
                                  np.asarray(res.node)), engine


# --------------------------------------------------------------------- #
# rendering + correlator
# --------------------------------------------------------------------- #

class TestRenderAndCorrelator:
    def test_message_is_kube_style_dominant_first(self):
        msg = render_unschedulable(5000, {"fit": 3200, "taints": 1800})
        assert msg == ("0/5000 nodes are available: 3200 Insufficient "
                       "resources, 1800 node(s) had taints that the pod "
                       "didn't tolerate.")

    def test_feasible_but_not_admitted_never_claims_zero_nodes(self):
        # a gang-rejected (or contention-lost) pod is individually
        # feasible — the message must say so, not "0/N available"
        msg = render_unschedulable(100, {}, feasible_nodes=40)
        assert msg.startswith("40/100 nodes are available but")
        assert "not admitted" in msg
        assert reason_fingerprint({}, feasible_nodes=40) == "not-admitted"
        assert reason_fingerprint({"fit": 5}, feasible_nodes=0) \
            != "not-admitted"

    def test_wave_event_budget_caps_synchronous_writes(self, monkeypatch):
        emitted = []
        expl = DecisionExplainer(name="t")
        expl.WAVE_EVENT_BUDGET = 2

        class _Sink:
            def emit(self, ns, name, reason, message, fingerprint=""):
                emitted.append(name)
                return True

        expl.sink = _Sink()
        doc = {"reasons": {"fit": 3}, "feasible_nodes": 0, "message": "m"}
        wb = [expl.WAVE_EVENT_BUDGET]
        for i in range(5):
            expl._maybe_emit(_pod(i), dict(doc), wb)
        assert len(emitted) == 2  # the cap held THIS wave
        # deferred, never starved: every pod's first event lands within a
        # few more waves (capped pods re-arm for their next occurrence)
        for _ in range(8):
            wb = [expl.WAVE_EVENT_BUDGET]
            for i in range(5):
                expl._maybe_emit(_pod(i), dict(doc), wb)
            if {f"p{i}" for i in range(5)} <= set(emitted):
                break
        assert {f"p{i}" for i in range(5)} <= set(emitted)

    def test_fingerprint_stable_under_count_jitter(self):
        a = reason_fingerprint({"fit": 3200, "taints": 1800})
        b = reason_fingerprint({"fit": 3100, "taints": 1900})
        assert a == b
        # a new failure MODE (dominance flip or new predicate) re-keys
        assert a != reason_fingerprint({"fit": 100, "taints": 1900})
        assert a != reason_fingerprint({"fit": 3200})

    def test_correlator_exponential_backoff_by_occurrence(self):
        c = ReasonCorrelator()
        emitted = [i + 1 for i in range(40)
                   if c.should_emit("default/p", "fp")]
        assert emitted == [1, 2, 4, 8, 16, 32]

    def test_correlator_forget_and_bound(self):
        c = ReasonCorrelator(max_keys=4)
        assert c.should_emit("k", "fp")       # occurrence 1 emits
        assert c.should_emit("k", "fp")       # occurrence 2 emits
        assert not c.should_emit("k", "fp")   # 3 suppressed (next at 4)
        c.forget("k")
        assert c.should_emit("k", "fp")  # fresh after forget
        for i in range(8):
            c.should_emit(f"other{i}", "fp")
        assert len(c._seen) <= 4


# --------------------------------------------------------------------- #
# the wave feed: /debug/why docs, metrics, flight-recorder record
# --------------------------------------------------------------------- #

class TestExplainerWave:
    def test_unschedulable_pod_attribution_and_resolution(self,
                                                          monkeypatch):
        from kubernetes_tpu.sched.metrics import UNSCHEDULABLE_REASONS

        before = UNSCHEDULABLE_REASONS.total()
        clk = {"t": 0.0}
        s = _scheduler(monkeypatch, clk=clk)
        s.on_pod_add(_pod(0))
        s.on_pod_add(_pod(1, cpu="99999"))
        st = s.schedule_pending()
        assert st.scheduled == 1 and st.unschedulable == 1
        doc = s.explainer.why("default/p1")
        assert doc["outcome"] == "unschedulable"
        assert doc["reasons"] == {"fit": 8}
        assert doc["valid_nodes"] == 8 and doc["feasible_nodes"] == 0
        assert doc["message"].startswith(
            "0/8 nodes are available: 8 Insufficient resources")
        assert UNSCHEDULABLE_REASONS.total() >= before + 8
        # wave record carries the attribution (flight recorder)
        rec = s.telemetry.recorder.records()[-1]
        assert rec["explain"]["reasons_total"] == {"fit": 8}
        assert "default/p1" in rec["explain"]["pods"]
        # pods that bound first try stay off the why surface (the happy
        # path must not pay per-pod host work)
        assert s.explainer.why("default/p0") is None
        # resolution: grow capacity so the pod fits — the stale failure
        # doc flips to the winning breakdown
        s.on_node_add(Node_("big", cpu="999999"))
        clk["t"] += 61.0
        st2 = s.schedule_pending()
        assert st2.scheduled == 1
        doc2 = s.explainer.why("default/p1")
        assert doc2["outcome"] == "scheduled"
        assert doc2["node"] == "big"
        assert set(doc2["score_parts"]) == set(EXPLAIN_SCORE_COMPONENTS)

    def test_kill_switch_builds_no_explainer(self, monkeypatch):
        s = _scheduler(monkeypatch, explain=False)
        assert s.explainer is None
        s.on_pod_add(_pod(0))
        st = s.schedule_pending()
        assert st.scheduled == 1
        assert "explain" not in s.telemetry.recorder.records()[-1]

    def test_build_explainer_env_parse(self, monkeypatch):
        monkeypatch.delenv("KTPU_EXPLAIN", raising=False)
        assert build_explainer() is None
        monkeypatch.setenv("KTPU_EXPLAIN", "0")
        assert build_explainer() is None
        monkeypatch.setenv("KTPU_EXPLAIN", "1")
        assert build_explainer() is not None


# --------------------------------------------------------------------- #
# events through the apiserver + TTL-bounded storage
# --------------------------------------------------------------------- #

class TestEvents:
    def _cluster(self):
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.client.rest import Client

        api = APIServer()
        return api, Client.local(api)

    def test_failed_scheduling_event_flow_and_dedupe(self, monkeypatch):
        api, client = self._cluster()
        clk = {"t": 0.0}
        s = _scheduler(monkeypatch, clk=clk)
        s.explainer.sink = APIEventSink(client, component="test-sched")
        s.on_pod_add(_pod(0, cpu="99999"))
        verdicts = 0
        for _ in range(9):
            st = s.schedule_pending()
            verdicts += st.unschedulable
            clk["t"] += 61.0
            s.queue.move_all_to_active(clk["t"])
            s.queue.pump(clk["t"])
        assert verdicts == 9
        evs = client.events.list("default")["items"]
        failed = [e for e in evs if e["reason"] == "FailedScheduling"]
        # ONE event object, count-bumped on re-emissions (1, 2, 4, 8)
        assert len(failed) == 1
        ev = failed[0]
        assert ev["count"] == 4
        assert ev["message"].startswith(
            "0/8 nodes are available: 8 Insufficient resources")
        assert ev["involvedObject"]["name"] == "p0"
        assert s.explainer.events_deduped == 9 - 4

    def test_events_store_is_ttl_bounded(self):
        api, client = self._cluster()
        client.events.create({
            "apiVersion": "v1", "kind": "Event",
            "metadata": {"name": "old-ev", "namespace": "default"},
            "reason": "FailedScheduling", "message": "old",
            "lastTimestamp": "2000-01-01T00:00:00Z", "count": 1,
        }, "default")
        client.events.create({
            "apiVersion": "v1", "kind": "Event",
            "metadata": {"name": "fresh-ev", "namespace": "default"},
            "reason": "FailedScheduling", "message": "fresh",
            "count": 1,
        }, "default")
        names = [e["metadata"]["name"]
                 for e in client.events.list("default")["items"]]
        assert "fresh-ev" in names and "old-ev" not in names
        from kubernetes_tpu.machinery import errors

        with pytest.raises(errors.StatusError) as ei:
            client.events.get("old-ev", "default")
        assert ei.value.code == 404

    def test_parse_rfc3339_offsets(self):
        from kubernetes_tpu.machinery.meta import parse_rfc3339

        base = parse_rfc3339("2026-08-04T12:00:00Z")
        assert base is not None
        # +05:00 means the instant is 5h EARLIER in UTC
        assert parse_rfc3339("2026-08-04T12:00:00+05:00") == base - 5 * 3600
        assert parse_rfc3339("2026-08-04T12:00:00-02:30") == \
            base + 2 * 3600 + 30 * 60
        assert parse_rfc3339("2026-08-04T12:00:00.123Z") == base
        assert parse_rfc3339("garbage") is None
        assert parse_rfc3339(None) is None

    def test_ttl_applies_to_events_only(self):
        api, client = self._cluster()
        # a pod with an ancient creationTimestamp must NOT be TTL-swept
        client.pods.create({
            "apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": "ancient",
                         "creationTimestamp": "2000-01-01T00:00:00Z"},
            "spec": {"containers": [{"name": "c", "image": "i"}]},
        }, "default")
        assert client.pods.get("ancient", "default")

    def test_sink_retry_budget_absorbs_pushback(self, monkeypatch):
        from kubernetes_tpu.client.rest import RetryPolicy
        from kubernetes_tpu.machinery import errors

        api, client = self._cluster()
        calls = {"n": 0}
        real_create = client.events.create

        def flaky(body, ns):
            calls["n"] += 1
            if calls["n"] == 1:
                raise errors.new_too_many_requests("busy", retry_seconds=0)
            return real_create(body, ns)

        sink = APIEventSink(client, retry=RetryPolicy(
            attempts=2, base_s=0.0, cap_s=0.0, deadline_s=5.0))
        monkeypatch.setattr(client.events, "create", flaky)
        assert sink.emit("default", "p0", "FailedScheduling", "msg", "fp")
        assert calls["n"] == 2
        assert sink.writes == 1 and sink.errors == 0


# --------------------------------------------------------------------- #
# the why-pending debug endpoint
# --------------------------------------------------------------------- #

class TestDebugWhy:
    def test_endpoint_serves_attribution_and_queue_state(self,
                                                         monkeypatch):
        from kubernetes_tpu.sched.server import TelemetryGateway

        clk = {"t": 0.0}
        s = _scheduler(monkeypatch, clk=clk)
        s.on_pod_add(_pod(0, cpu="99999"))
        clk["t"] = 5.0
        s.schedule_pending()
        gw = TelemetryGateway(s.telemetry, scheduler=s).start()
        try:
            clk["t"] = 7.0
            with urllib.request.urlopen(
                    gw.url + "/debug/why/default/p0") as r:
                doc = json.loads(r.read())
            assert doc["pod"] == "default/p0"
            assert doc["explain_enabled"] is True
            assert doc["queue_lane"] == "unschedulable"
            assert doc["attempts"] == 1
            assert doc["first_seen_age_s"] == pytest.approx(7.0)
            att = doc["attribution"]
            assert att["reasons"] == {"fit": 8}
            assert att["message"].startswith("0/8 nodes are available")
            with pytest.raises(Exception) as ei:
                urllib.request.urlopen(gw.url + "/debug/why/default/ghost")
            assert getattr(ei.value, "code", None) == 404
        finally:
            gw.stop()


# --------------------------------------------------------------------- #
# the degraded-wave reconstruction drill (acceptance)
# --------------------------------------------------------------------- #

@pytest.mark.chaos
class TestDegradedWaveReconstruction:
    def test_last_dump_alone_reconstructs_what_and_why(self, monkeypatch):
        clk = {"t": 0.0}
        s = _scheduler(monkeypatch, clk=clk)
        for i in range(5):
            s.on_pod_add(_pod(i))
        s.on_pod_add(_pod(9, cpu="99999"))
        # primary dies once; the CPU fallback serves the wave — a DEGRADED
        # wave, and a flight-recorder dump trigger
        faultline.install("device.error@cycle:1")
        st = s.schedule_pending()
        assert st.scheduled == 5 and st.unschedulable == 1
        dump = s.telemetry.last_dump
        assert dump is not None and dump["trigger"] == "degraded"
        doc = json.loads(json.dumps(dump))  # structured JSON end to end
        rec = doc["records"][-1]
        kinds = [k for k, _ in rec["supervisor_events"]]
        assert "degraded" in kinds
        # WHAT the wave placed...
        assert rec["stats"]["scheduled"] == 5
        assert rec["stats"]["unschedulable"] == 1
        # ...and WHY the rest failed: per-predicate counts in the record
        assert rec["explain"]["reasons_total"] == {"fit": 8}
        assert rec["explain"]["pods"]["default/p9"]["reasons"] == {"fit": 8}
        assert rec["explain"]["pods"]["default/p9"]["feasible"] == 0


# --------------------------------------------------------------------- #
# fleet: per-tenant attribution
# --------------------------------------------------------------------- #

@pytest.mark.fleet
class TestFleetExplain:
    def test_attribution_is_per_tenant(self, monkeypatch):
        from kubernetes_tpu.fleet import FleetServer
        from kubernetes_tpu.state.dims import Dims

        monkeypatch.setenv("KTPU_EXPLAIN", "1")
        clk = {"t": 0.0}
        srv = FleetServer(batch_size=32, base_dims=Dims(N=8, P=32, E=64),
                          clock=lambda: clk["t"])
        srv.prewarmer.enabled = False
        nodes = make_nodes(4)
        for k in range(2):
            t = srv.add_tenant(f"t{k:02d}")
            for n in nodes:
                t.on_node_add(n)
        # cpu=64 fits under t00's DRF headroom (dominant demand 64/128 ≤
        # quota 1.0, so the clamp admits it) but no single 32-cpu node
        # holds it — a genuine fit rejection on every node, attributed
        # per tenant
        srv.tenant("t00").on_pod_add(Pod(
            name="p0", requests=Resources.make(cpu="64", memory="16Mi"),
            creation_index=0))
        srv.tenant("t01").on_pod_add(_pod(0))
        tick = srv.tick()
        assert tick.per_tenant["t00"].unschedulable == 1
        assert tick.per_tenant["t01"].scheduled == 1
        doc = srv.tenant("t00").sched.explainer.why("default/p0")
        assert doc is not None and doc["reasons"] == {"fit": 4}
        # tenant isolation: t01's explainer never saw t00's pod
        assert srv.tenant("t01").sched.explainer.why("default/p0") is None


# --------------------------------------------------------------------- #
# satellite: KTPU_FLIGHT_RING
# --------------------------------------------------------------------- #

class TestFlightRing:
    def test_env_sets_capacity(self, monkeypatch):
        from kubernetes_tpu.sched.telemetry import SchedulerTelemetry

        monkeypatch.setenv("KTPU_FLIGHT_RING", "7")
        tel = SchedulerTelemetry(enabled=True)
        assert tel.recorder.capacity == 7
        for i in range(10):
            tel.recorder.record({"i": i})
        assert len(tel.recorder.records()) == 7
        assert tel.recorder.evicted == 3

    @pytest.mark.parametrize("raw,expect", [
        ("", 64), ("garbage", 64), ("0", 1), ("-5", 1),
        ("1", 1), ("128", 128), ("9999999", 65536),
    ])
    def test_bounds_checked_parse(self, monkeypatch, raw, expect):
        from kubernetes_tpu.sched.telemetry import flight_ring_capacity

        monkeypatch.setenv("KTPU_FLIGHT_RING", raw)
        assert flight_ring_capacity() == expect

    def test_explicit_capacity_wins_over_env(self, monkeypatch):
        from kubernetes_tpu.sched.telemetry import SchedulerTelemetry

        monkeypatch.setenv("KTPU_FLIGHT_RING", "7")
        tel = SchedulerTelemetry(capacity=3, enabled=True)
        assert tel.recorder.capacity == 3


# --------------------------------------------------------------------- #
# satellite: docs metric-catalogue drift gate
# --------------------------------------------------------------------- #

class TestDocDrift:
    def test_catalogue_and_registry_agree(self):
        # importing the registering modules populates the shared registry
        import kubernetes_tpu.apiserver.server  # noqa: F401
        import kubernetes_tpu.client.informers  # noqa: F401
        import kubernetes_tpu.sched.explain  # noqa: F401
        import kubernetes_tpu.sched.metrics  # noqa: F401
        from kubernetes_tpu.component.metrics import DEFAULT_REGISTRY

        doc_path = os.path.join(os.path.dirname(__file__), "..", "docs",
                                "OBSERVABILITY.md")
        with open(doc_path) as f:
            text = f.read()
        registered = {n for n in DEFAULT_REGISTRY._metrics
                      if n.startswith(("scheduler_", "apiserver_"))}
        # every doc-named scheduler_*/apiserver_* token must be registered
        doc_names = {m.split("{")[0] for m in re.findall(
            r"`((?:scheduler|apiserver)_[a-z0-9_]+(?:\{[^}]*\})?)`", text)}
        unregistered = doc_names - registered
        assert not unregistered, (
            f"docs/OBSERVABILITY.md names unregistered metrics: "
            f"{sorted(unregistered)}")
        # every registered metric must appear in the catalogue
        undocumented = registered - doc_names
        assert not undocumented, (
            f"registered metrics missing from the docs/OBSERVABILITY.md "
            f"catalogue: {sorted(undocumented)}")


# --------------------------------------------------------------------- #
# satellite: bench trend tool
# --------------------------------------------------------------------- #

class TestBenchTrend:
    @staticmethod
    def _artifact(tmp_path, n, stages):
        doc = {"metric": "m", "value": 1.0, "unit": "pods/s",
               "vs_baseline": 1.0, "detail": {"stages": stages}}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(doc))

    @staticmethod
    def _stage(**kw):
        base = {"nodes": 1000, "pods": 10000, "kind": "explain", "ok": True,
                "pods_per_sec": 1000.0, "cycle_seconds": 1.0,
                "attribution_overhead_pct": 1.0}
        base.update(kw)
        return base

    def test_no_regression_exits_zero(self, tmp_path, capsys):
        from scripts.bench_trend import main

        self._artifact(tmp_path, 1, [self._stage()])
        self._artifact(tmp_path, 2, [self._stage(pods_per_sec=1010.0)])
        assert main(["--dir", str(tmp_path)]) == 0
        assert "no budget-metric regressions" in capsys.readouterr().out

    def test_budget_metric_regression_exits_nonzero(self, tmp_path,
                                                    capsys):
        from scripts.bench_trend import main

        self._artifact(tmp_path, 1, [self._stage()])
        # a "<=" budget metric doubling is a regression past 25% tolerance
        self._artifact(tmp_path, 2, [self._stage(
            attribution_overhead_pct=2.0)])
        assert main(["--dir", str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "REGRESSION" in out and "attribution_overhead_pct" in out

    def test_throughput_drop_is_a_regression(self, tmp_path):
        from scripts.bench_trend import main

        self._artifact(tmp_path, 1, [self._stage()])
        self._artifact(tmp_path, 2, [self._stage(pods_per_sec=100.0)])
        assert main(["--dir", str(tmp_path)]) == 1

    def test_single_artifact_is_a_noop(self, tmp_path):
        from scripts.bench_trend import main

        self._artifact(tmp_path, 1, [self._stage()])
        assert main(["--dir", str(tmp_path)]) == 0
