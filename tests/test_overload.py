"""Overload governor / brownout robustness (ISSUE 9, `overload` marker):
the mode ladder with hysteresis, priority-aware shedding into the deferred
lane, adaptive wave sizing, the commit-path circuit breaker (incl. the
mid-wave cut and the dispatch pause), the apiserver max-inflight filter's
429 + Retry-After, the client/binder retry budgets, and the kill switch's
bit-equality contract. Deterministic clocks throughout."""

import threading

import pytest

from kubernetes_tpu.api.types import Node, Pod, Resources
from kubernetes_tpu.sched.overload import (
    CLOSED,
    HALF_OPEN,
    NORMAL,
    OPEN,
    SHED_LOW,
    TRICKLE,
    CommitBreaker,
    OverloadConfig,
    OverloadGovernor,
)
from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

pytestmark = pytest.mark.overload


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def mkpod(name, priority=0, creation=0, cpu="100m"):
    return Pod(name=name, priority=priority, creation_index=creation,
               requests=Resources.make(cpu=cpu, memory="64Mi"))


def mknode(name, cpu=64):
    return Node(name=name, allocatable=Resources.make(
        cpu=cpu, memory="64Gi", pods=110))


def _cfg(**kw):
    base = dict(shed_enter_pressure=2.0, shed_exit_pressure=1.0,
                trickle_enter_pressure=8.0, trickle_exit_pressure=4.0,
                exit_dwell_s=1.0, shed_priority_cutoff=50,
                target_cycle_s=1.0, min_wave=4, trickle_wave=4,
                slow_streak=2, fail_threshold=3, latency_slo_s=5.0,
                latency_min_samples=4, cooldown_s=1.0, cooldown_cap_s=8.0,
                probe_successes=2)
    base.update(kw)
    return OverloadConfig(**base)


def _gov(batch=16, clock=None, **kw):
    clock = clock or FakeClock()
    events = []
    g = OverloadGovernor(batch, cfg=_cfg(**kw), clock=clock,
                         event_sink=lambda k, d: events.append((k, d)))
    g._test_events = events
    return g, clock


def depths(active=0, backoff=0, unsched=0, deferred=0):
    return {"active": active, "backoff": backoff,
            "unschedulable": unsched, "deferred": deferred}


class TestModeLadder:
    def test_pressure_alone_does_not_ascend(self):
        """A bulk backlog drained at full speed (high pressure, fast
        cycles) is throughput, not overload — NORMAL holds."""
        g, clk = _gov()
        for _ in range(10):
            d = g.begin_wave(clk.advance(0.1), depths(active=1000))
            assert d.mode == NORMAL and d.shed_below is None
            g.end_wave(clk.t, 16, 0.1)  # fast waves: no slow streak

    def test_pressure_plus_slow_streak_enters_shed(self):
        g, clk = _gov()
        g.end_wave(clk.t, 16, 5.0)
        g.end_wave(clk.t, 16, 5.0)  # two slow waves = falling behind
        d = g.begin_wave(clk.advance(0.1), depths(active=64))
        assert d.mode == SHED_LOW
        assert d.shed_below == 50
        assert g.mode_transitions == 1

    def test_trickle_and_hysteresis_descent(self):
        g, clk = _gov()
        g.end_wave(clk.t, 16, 5.0)
        g.end_wave(clk.t, 16, 5.0)
        d = g.begin_wave(clk.advance(0.1), depths(active=16 * 10))
        assert d.mode == TRICKLE
        assert d.wave_limit == 4  # trickle_wave
        # pressure drops below the exit bound, but the dwell must elapse
        d = g.begin_wave(clk.advance(0.1), depths(active=8))
        assert d.mode == TRICKLE
        d = g.begin_wave(clk.advance(1.1), depths(active=8))
        assert d.mode == SHED_LOW  # one rung at a time
        # each rung serves its own dwell: the first post-descent wave
        # starts the clock, the next one past it steps down
        d = g.begin_wave(clk.advance(0.1), depths(active=8))
        assert d.mode == SHED_LOW
        d = g.begin_wave(clk.advance(1.1), depths(active=8))
        assert d.mode == NORMAL
        assert d.release_deferred  # leaving shedding re-admits the lane

    def test_oscillating_pressure_does_not_flap(self):
        g, clk = _gov()
        g.end_wave(clk.t, 16, 5.0)
        g.end_wave(clk.t, 16, 5.0)
        g.begin_wave(clk.advance(0.1), depths(active=64))
        assert g.mode == SHED_LOW
        # bouncing just under/over the exit bound resets the dwell; the
        # mode holds instead of flapping
        for i in range(6):
            g.begin_wave(clk.advance(0.3),
                         depths(active=8 if i % 2 else 64))
        assert g.mode == SHED_LOW


class TestAdaptiveWaveSizing:
    def test_normal_mode_never_resizes(self):
        g, clk = _gov(batch=64)
        g.end_wave(clk.t, 64, 99.0)
        assert g.wave_limit() == 64  # observer only while NORMAL

    def test_shrink_and_grow_back_pow2(self):
        g, clk = _gov(batch=64)
        g.end_wave(clk.t, 64, 5.0)
        g.end_wave(clk.t, 64, 5.0)
        g.begin_wave(clk.advance(0.1), depths(active=200))
        assert g.mode == SHED_LOW
        g.end_wave(clk.t, 64, 5.0)   # over deadline → halve
        assert g.wave_limit() == 32
        g.end_wave(clk.t, 32, 5.0)
        g.end_wave(clk.t, 32, 5.0)
        assert g.wave_limit() == 8
        g.end_wave(clk.t, 8, 5.0)
        assert g.wave_limit() == 4   # min_wave floor
        # healthy waves grow it back on the pow2 ladder
        for _ in range(8):
            g.end_wave(clk.t, 4, 0.1)
        assert g.wave_limit() in (16, 32, 64)
        # exit to NORMAL restores the configured batch
        g.begin_wave(clk.advance(0.1), depths(active=1))
        g.begin_wave(clk.advance(1.1), depths(active=1))
        assert g.mode == NORMAL
        g.end_wave(clk.t, 4, 0.1)
        assert g.wave_limit() == 64


class TestCommitBreaker:
    def test_opens_on_consecutive_failures(self):
        clk = FakeClock()
        b = CommitBreaker(_cfg(), clock=clk)
        for _ in range(2):
            b.note(False, 0.01)
        assert b.state == CLOSED
        b.note(False, 0.01)
        assert b.state == OPEN
        assert b.opens == 1

    def test_opens_on_latency_slo(self):
        clk = FakeClock()
        b = CommitBreaker(_cfg(latency_slo_s=0.1, latency_min_samples=4),
                          clock=clk)
        for _ in range(6):
            b.note(True, 0.5)  # successful but slow
        assert b.state == OPEN

    def test_half_open_probe_closes_and_reopens(self):
        clk = FakeClock()
        b = CommitBreaker(_cfg(), clock=clk)
        for _ in range(3):
            b.note(False, 0.01)
        assert b.allow(clk.t) == (False, False)      # still cooling down
        allowed, probe = b.allow(clk.advance(1.1))
        assert (allowed, probe) == (True, True)      # half-open probe
        b.note(False, 0.01)                          # probe fails
        assert b.state == OPEN
        assert b._cooldown == 2.0                    # doubled
        b.allow(clk.advance(2.1))
        b.note(True, 0.01)
        b.note(True, 0.01)                           # 2 probes ok
        assert b.state == CLOSED
        assert b.closes == 1
        assert b._cooldown == 1.0                    # reset

    def test_slow_probe_does_not_close(self):
        clk = FakeClock()
        b = CommitBreaker(_cfg(latency_slo_s=0.1, latency_min_samples=2),
                          clock=clk)
        b.note(True, 5.0)
        b.note(True, 5.0)
        assert b.state == OPEN
        b.allow(clk.advance(1.1))
        assert b.state == HALF_OPEN
        b.note(True, 5.0)   # successful but still over the SLO
        assert b.state == OPEN

    def test_breaker_open_forces_trickle_and_pause(self):
        g, clk = _gov()
        for _ in range(3):
            g.note_commit(False, 0.01)
        d = g.begin_wave(clk.advance(0.1), depths(active=4))
        assert g.mode == TRICKLE
        assert not d.dispatch_allowed
        assert g.paused_waves == 1
        # cooldown expiry admits a trickle-sized probe
        d = g.begin_wave(clk.advance(1.1), depths(active=4))
        assert d.dispatch_allowed and d.probe
        assert d.wave_limit == 4


def _sched(clock, batch=8, n_nodes=4, binder=None, cfg=None):
    s = Scheduler(binder=binder or RecordingBinder(), batch_size=batch,
                  clock=clock)
    s.prewarmer.enabled = False
    if cfg is not None:
        s.governor = OverloadGovernor(
            batch, cfg=cfg, clock=clock,
            event_sink=s.telemetry.note_supervisor_event)
    for i in range(n_nodes):
        s.on_node_add(mknode(f"n{i}"))
    return s


class TestSchedulerIntegration:
    def test_shed_parks_low_priority_and_releases(self):
        clk = FakeClock()
        s = _sched(clk, batch=8, cfg=_cfg(shed_enter_pressure=0.5,
                                          target_cycle_s=10.0))
        # force SHED_LOW directly (mode mechanics are unit-tested above)
        s.governor._set_mode(SHED_LOW, "test")
        for i in range(6):
            s.on_pod_add(mkpod(f"lo-{i}", priority=0, creation=i))
        for i in range(2):
            s.on_pod_add(mkpod(f"hi-{i}", priority=100, creation=10 + i))
        st = s.schedule_pending(now=clk.advance(0.1))
        # high-priority bound; low-priority parked, not failed
        assert st.scheduled == 2
        assert st.shed == 6
        assert st.unschedulable == 0
        assert s.queue.depths()["deferred"] == 6
        assert {k for k, _ in s.binder.bound} == {
            "default/hi-0", "default/hi-1"}
        # recovery: pressure low → dwell → NORMAL → deferred released
        s.governor._healthy_since = None
        s.schedule_pending(now=clk.advance(0.1))
        st = s.schedule_pending(now=clk.advance(2.0))
        assert s.governor.mode == NORMAL
        total = s.run_until_idle()
        assert s.queue.depths()["deferred"] == 0
        assert len(s.binder.bound) == 8  # every shed pod admitted
        assert total.unschedulable == 0

    def test_breaker_pauses_dispatch_no_device_time(self):
        clk = FakeClock()
        s = _sched(clk, cfg=_cfg())
        for _ in range(3):
            s.governor.note_commit(False, 0.01)
        assert s.governor.breaker.state == OPEN
        for i in range(4):
            s.on_pod_add(mkpod(f"p{i}", creation=i))
        st = s.schedule_pending(now=clk.advance(0.1))
        assert st.commit_paused == 1
        assert st.attempted == 0                  # nothing popped
        assert s.queue.lengths()[0] == 4          # nothing lost
        assert s.binder.bound == []
        # half-open probe wave binds again and closes the breaker
        st = s.schedule_pending(now=clk.advance(1.1))
        assert st.scheduled >= 2
        assert s.governor.breaker.state == CLOSED

    def test_mid_wave_breaker_cut_requeues_remainder(self):
        clk = FakeClock()

        class FailingBinder(RecordingBinder):
            def bind(self, pod, node_name):
                return False

        s = _sched(clk, batch=16, binder=FailingBinder(),
                   cfg=_cfg(fail_threshold=3))
        for i in range(10):
            s.on_pod_add(mkpod(f"p{i}", creation=i))
        st = s.schedule_pending(now=clk.advance(0.1))
        # 3 failures trip the breaker; the rest requeue promptly without
        # burning the commit path or earning a failure verdict
        assert st.bind_errors == 3
        assert st.requeued == 7
        assert s.governor.breaker.state == OPEN
        d = s.queue.depths()
        # 3 bind-error verdicts parked, 7 promptly retryable — all 10 live
        assert sum(d.values()) == 10              # nothing lost

    def test_mid_wave_breaker_cut_with_a_window_of_writes_in_flight(self):
        """A binder with a window (ISSUE 40): the breaker is asked before
        each hand-out and fed at each gather, so when this wave's failures
        open it at most `width` further writes were made, those are
        settled, and the rest requeues with no verdict."""
        import time

        from kubernetes_tpu.sched.server import BindWindow

        width = 4

        class FailingWindowed(RecordingBinder):
            def __init__(self):
                super().__init__()
                self.written = []
                self.windowed = BindWindow(self.bind, width)

            def bind(self, pod, node_name):
                self.written.append(
                    (pod.key, threading.current_thread().name))
                time.sleep(0.002)
                return False

            def window(self):
                return self.windowed

        clk = FakeClock()
        binder = FailingWindowed()
        s = _sched(clk, batch=64, binder=binder, cfg=_cfg(fail_threshold=3))
        for i in range(40):
            s.on_pod_add(mkpod(f"p{i}", creation=i))
        try:
            st = s.schedule_pending(now=clk.advance(0.1))
        finally:
            binder.windowed.close()
        assert s.governor.breaker.state == OPEN
        assert 3 <= len(binder.written) <= 3 + width
        assert {t for _, t in binder.written} <= {
            f"bind-window-{i}" for i in range(width)}
        assert len({k for k, _ in binder.written}) == len(binder.written)
        # every write was answered and settled; the rest went back unjudged
        assert st.bind_errors == len(binder.written)
        assert st.requeued == 40 - len(binder.written)
        assert sum(s.queue.depths().values()) == 40   # nothing lost
        assert s.cache.pod_count == 0                 # nothing left assumed

    def test_kill_switch_bit_equal(self, monkeypatch):
        def run(overload):
            if overload:
                monkeypatch.delenv("KTPU_OVERLOAD", raising=False)
            else:
                monkeypatch.setenv("KTPU_OVERLOAD", "0")
            clk = FakeClock()
            s = _sched(clk, batch=8)
            if overload:
                assert s.governor is not None
            else:
                assert s.governor is None
            for i in range(24):
                s.on_pod_add(mkpod(f"p{i}", priority=i % 3, creation=i))
            total = s.run_until_idle()
            return dict(total.assignments)

        a = run(True)
        b = run(False)
        assert a == b and len(a) == 24

    def test_wave_limit_clamps_pop(self):
        clk = FakeClock()
        s = _sched(clk, batch=8, cfg=_cfg())
        s.governor._set_mode(TRICKLE, "test")
        for i in range(20):
            s.on_pod_add(mkpod(f"p{i}", priority=100, creation=i))
        st = s.schedule_pending(now=clk.advance(0.1))
        assert st.attempted == 4  # trickle_wave, not batch_size


class TestMaxInflightFilter:
    def _api(self, **kw):
        from kubernetes_tpu.apiserver.server import APIServer

        return APIServer(**kw)

    def test_readonly_limit_429_with_retry_after(self):
        from kubernetes_tpu.apiserver.server import handle_rest
        from kubernetes_tpu.machinery import errors

        api = self._api(max_inflight=1)
        # saturate the lane from another thread parked inside a handler
        entered = threading.Event()
        release = threading.Event()
        orig_acquire = api.inflight.acquire
        assert orig_acquire(False)        # hold the one readonly slot
        with pytest.raises(errors.StatusError) as ei:
            handle_rest(api, "GET", "/api/v1/nodes", {}, None)
        assert ei.value.code == 429
        assert ei.value.details.get("retryAfterSeconds") == 1
        api.inflight.release(False)
        code, _ = handle_rest(api, "GET", "/api/v1/nodes", {}, None)
        assert code == 200
        assert api.inflight.rejected == 1
        del entered, release

    def test_mutating_limit_separate_lane(self):
        from kubernetes_tpu.apiserver.server import handle_rest
        from kubernetes_tpu.machinery import errors

        api = self._api(max_mutating_inflight=1)
        assert api.inflight.acquire(True)
        # reads pass (separate lane); writes shed
        code, _ = handle_rest(api, "GET", "/api/v1/nodes", {}, None)
        assert code == 200
        with pytest.raises(errors.StatusError) as ei:
            handle_rest(api, "POST", "/api/v1/namespaces/default/configmaps",
                        {}, {"metadata": {"name": "x"}})
        assert ei.value.code == 429
        api.inflight.release(True)
        code, _ = handle_rest(
            api, "POST", "/api/v1/namespaces/default/configmaps",
            {}, {"metadata": {"name": "x"}})
        assert code == 201

    def test_watches_exempt(self):
        from kubernetes_tpu.apiserver.server import handle_rest

        api = self._api(max_inflight=1)
        assert api.inflight.acquire(False)  # lane full
        tag, w = handle_rest(api, "GET", "/api/v1/pods",
                             {"watch": "true"}, None)
        assert tag == "WATCH"               # long-running exemption
        w.stop()
        api.inflight.release(False)

    def test_inflight_releases_on_error(self):
        from kubernetes_tpu.apiserver.server import handle_rest
        from kubernetes_tpu.machinery import errors

        api = self._api(max_inflight=2)
        for _ in range(4):
            with pytest.raises(errors.StatusError):
                handle_rest(api, "GET", "/api/v1/nodes/nope", {}, None)
        assert api.inflight._inflight == 0  # never leaked a slot


class TestRetryBudgets:
    def test_retry_policy_honors_retry_after_and_gives_up(self):
        from kubernetes_tpu.client.rest import RetryPolicy
        from kubernetes_tpu.machinery import errors

        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise errors.new_too_many_requests("busy", retry_seconds=0)
            return {"ok": True}

        out = RetryPolicy(attempts=3, base_s=0.001, cap_s=0.002,
                          deadline_s=5.0).run(flaky)
        assert out == {"ok": True} and len(calls) == 3

        calls.clear()

        def always_429():
            calls.append(1)
            raise errors.new_too_many_requests("busy", retry_seconds=0)

        with pytest.raises(errors.StatusError):
            RetryPolicy(attempts=2, base_s=0.001,
                        deadline_s=5.0).run(always_429)
        assert len(calls) == 3  # first try + 2 retries, then surrender

    def test_retry_policy_does_not_retry_conflicts(self):
        from kubernetes_tpu.client.rest import RetryPolicy
        from kubernetes_tpu.machinery import errors

        calls = []

        def conflict():
            calls.append(1)
            raise errors.new_conflict("pods", "x", "nope")

        with pytest.raises(errors.StatusError):
            RetryPolicy(attempts=3, base_s=0.001).run(conflict)
        assert len(calls) == 1

    def test_local_transport_retry_absorbs_inflight_pushback(self):
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.client.rest import Client, RetryPolicy

        api = APIServer(max_inflight=1)
        client = Client.local(api, retry=RetryPolicy(
            attempts=3, base_s=0.001, cap_s=0.01, deadline_s=5.0))
        # occupy the slot briefly from another thread, then free it —
        # the retried request must land without the caller seeing a 429
        api.inflight.acquire(False)
        t = threading.Timer(0.02, lambda: api.inflight.release(False))
        t.start()
        try:
            out = client.nodes.list()
            assert out.get("kind", "").endswith("List")
        finally:
            t.join()

    def test_apibinder_retries_pushback(self):
        from kubernetes_tpu.machinery import errors
        from kubernetes_tpu.sched.server import APIBinder

        class FakePods:
            def __init__(self):
                self.calls = 0

            def bind(self, *a, **kw):
                self.calls += 1
                if self.calls < 3:
                    raise errors.new_too_many_requests("busy",
                                                       retry_seconds=0)
                return {}

        class FakeClient:
            pods = FakePods()

        b = APIBinder(FakeClient(), retry_budget=3, retry_base_s=0.001,
                      retry_cap_s=0.002, bind_deadline_s=5.0)
        assert b.bind(mkpod("a"), "n1")
        assert b.pushback_retries == 2

        FakeClient.pods = FakePods()
        b2 = APIBinder(FakeClient(), retry_budget=1, retry_base_s=0.001,
                       bind_deadline_s=5.0)
        assert not b2.bind(mkpod("a"), "n1")
        assert b2.pushback_failures == 1

    def test_apibinder_does_not_retry_fenced_409(self):
        from kubernetes_tpu.api.types import FENCED_BIND_MARKER
        from kubernetes_tpu.machinery import errors
        from kubernetes_tpu.sched.server import APIBinder

        class FencedPods:
            calls = 0

            def bind(self, *a, **kw):
                FencedPods.calls += 1
                raise errors.new_conflict("pods", "a",
                                          f"{FENCED_BIND_MARKER}: stale")

        class FakeClient:
            pods = FencedPods()

        b = APIBinder(FakeClient(), fence_source=lambda: 1)
        assert not b.bind(mkpod("a"), "n1")
        assert FencedPods.calls == 1
        assert b.stale_rejects == 1

    def test_a_fenced_409_counts_once_a_binding_under_16_threads(self):
        """`APIBinder.bind` is called from its window's threads (ISSUE 40):
        its counts lose no update however the threads take turns."""
        import sys

        from kubernetes_tpu.api.types import FENCED_BIND_MARKER
        from kubernetes_tpu.machinery import errors
        from kubernetes_tpu.sched.server import APIBinder

        class Pods:
            def bind(self, name, *a, **kw):
                if name.endswith("-busy"):
                    raise errors.new_too_many_requests("busy",
                                                       retry_seconds=0)
                raise errors.new_conflict("pods", name,
                                          f"{FENCED_BIND_MARKER}: stale")

        class FakeClient:
            pods = Pods()

        b = APIBinder(FakeClient(), fence_source=lambda: 1, retry_budget=1,
                      retry_base_s=0.0, bind_deadline_s=5.0)
        each, was = 150, sys.getswitchinterval()

        def bind_many(t):
            for i in range(each):
                assert not b.bind(mkpod(f"t{t}-{i}"), "n1")
                assert not b.bind(mkpod(f"t{t}-{i}-busy"), "n1")

        sys.setswitchinterval(1e-6)   # a turn a few bytecodes long
        try:
            threads = [threading.Thread(target=bind_many, args=(t,))
                       for t in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            sys.setswitchinterval(was)
        assert b.stale_rejects == 16 * each
        assert b.pushback_retries == 16 * each
        assert b.pushback_failures == 16 * each


class TestWatchTimeoutFix:
    def test_socket_timeout_derives_from_timeout_seconds(self, monkeypatch):
        """rest.py:158 regression: a 10 s watch must carry a ~10 s socket
        timeout, not the hardcoded +3600."""
        from kubernetes_tpu.client import rest as rest_mod

        captured = {}

        class _FakeResp:
            headers = {"Content-Type": "application/json"}

            def __iter__(self):
                return iter(())

            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

        def fake_urlopen(req, timeout=None, **kw):
            captured["timeout"] = timeout
            return _FakeResp()

        monkeypatch.setattr(rest_mod.urllib.request, "urlopen",
                            fake_urlopen)
        tr = rest_mod.HTTPTransport("http://127.0.0.1:1", timeout=5.0)
        w = tr.stream_watch("/api/v1/pods", {"timeoutSeconds": "10"})
        for _ in range(200):
            if "timeout" in captured:
                break
            import time as _t

            _t.sleep(0.01)
        w.stop()
        assert captured["timeout"] == 15.0  # self.timeout + timeoutSeconds
        # and the default stays the old 3600-ish shape
        captured.clear()
        w = tr.stream_watch("/api/v1/pods", {})
        for _ in range(200):
            if "timeout" in captured:
                break
            import time as _t

            _t.sleep(0.01)
        w.stop()
        assert captured["timeout"] == 3605.0

    def test_watch_verb_passes_timeout_seconds(self):
        from kubernetes_tpu.client.rest import ResourceClient

        class FakeTransport:
            def __init__(self):
                self.q = None

            def stream_watch(self, path, q):
                self.q = q
                return "watch"

        tr = FakeTransport()
        rc = ResourceClient(tr, "", "v1", "pods", True)
        rc.watch(timeout_seconds=10)
        assert tr.q["timeoutSeconds"] == "10"


class TestFlightRecorderNarration:
    def test_transitions_land_in_wave_records_and_dump(self):
        clk = FakeClock()
        s = _sched(clk, cfg=_cfg(fail_threshold=2))

        class FailingBinder(RecordingBinder):
            def bind(self, pod, node_name):
                return False

        s.binder = FailingBinder()
        for i in range(4):
            s.on_pod_add(mkpod(f"p{i}", creation=i))
        s.schedule_pending(now=clk.advance(0.1))
        recs = s.telemetry.recorder.records()
        events = [e for r in recs for e in r.get("supervisor_events", ())]
        kinds = {k for k, _ in events}
        assert "breaker_open" in kinds
        # breaker_open is a dump trigger: the brownout is in the artifact
        assert s.telemetry.last_dump is not None
        assert s.telemetry.last_dump["trigger"] == "breaker_open"

    def test_governor_metrics_exported(self):
        from kubernetes_tpu.component.metrics import DEFAULT_REGISTRY
        from kubernetes_tpu.sched import metrics as m

        clk = FakeClock()
        g = OverloadGovernor(8, cfg=_cfg(), clock=clk)
        g._set_mode(SHED_LOW, "test")
        g.note_shed(3)
        for _ in range(3):
            g.note_commit(False, 0.01)
        text = DEFAULT_REGISTRY.expose_text()
        assert "scheduler_overload_mode" in text
        assert "scheduler_commit_breaker_state" in text
        assert m.SHED_PODS.total() >= 3

    def test_queue_depth_gauges_include_deferred(self):
        from kubernetes_tpu.component.metrics import DEFAULT_REGISTRY
        from kubernetes_tpu.sched.metrics import observe_queue_depths

        observe_queue_depths({"active": 5, "backoff": 2,
                              "unschedulable": 1, "deferred": 7})
        text = DEFAULT_REGISTRY.expose_text()
        assert 'scheduler_pending_pods{queue="deferred"} 7' in text


class TestFleetTenantIsolation:
    def test_one_tenant_brownout_sheds_only_that_tenant(self):
        pytest.importorskip("jax")
        from kubernetes_tpu.fleet.server import FleetServer

        clk = FakeClock()
        fs = FleetServer(batch_size=8, clock=clk)
        ta = fs.add_tenant("ta")
        tb = fs.add_tenant("tb")
        for t in (ta, tb):
            for i in range(3):
                t.on_node_add(mknode(f"{t.name}-n{i}"))
        # tenant A's breaker is tripped; tenant B is healthy
        for _ in range(5):
            ta.sched.governor.note_commit(False, 0.01)
        assert ta.sched.governor.breaker.state == OPEN
        for i in range(4):
            ta.on_pod_add(mkpod(f"a{i}", creation=i))
            tb.on_pod_add(mkpod(f"b{i}", creation=i))
        tick = fs.tick(now=clk.advance(0.05))
        # A paused (nothing popped, nothing lost); B scheduled normally
        assert tick.per_tenant["ta"].commit_paused == 1
        assert tick.per_tenant["ta"].scheduled == 0
        assert ta.sched.queue.lengths()[0] == 4
        assert tick.per_tenant["tb"].scheduled == 4
