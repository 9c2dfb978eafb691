"""Lifecycle tests for the scheduler cache and the 3-queue PriorityQueue,
mirroring the table-driven cases of internal/cache/cache_test.go and
internal/queue/scheduling_queue_test.go."""

import pytest

from kubernetes_tpu.api.types import Node, Pod, Resources
from kubernetes_tpu.sched.queue import (
    INITIAL_BACKOFF,
    MAX_BACKOFF,
    UNSCHEDULABLE_FLUSH_INTERVAL,
    PriorityQueue,
)
from kubernetes_tpu.state.cache import CacheError, SchedulerCache
from kubernetes_tpu.state.encode import Encoder


def pod(name, priority=0, creation=0):
    return Pod(name=name, priority=priority, creation_index=creation,
               requests=Resources.make(cpu="100m", memory="64Mi"))


class TestSchedulerCache:
    def test_assume_confirm_lifecycle(self):
        c = SchedulerCache(ttl=30.0)
        c.add_node(Node(name="n1", allocatable=Resources.make(cpu=4, memory="8Gi")))
        p = pod("a")
        c.assume_pod(p, "n1")
        assert c.is_assumed("default/a")
        assert c.get_pod("default/a").node_name == "n1"
        # informer confirmation clears assumed
        bound = pod("a")
        bound.node_name = "n1"
        c.add_pod(bound)
        assert not c.is_assumed("default/a")
        assert c.counts() == (1, 1, 0)

    def test_assume_expire(self):
        c = SchedulerCache(ttl=30.0)
        p = pod("a")
        c.assume_pod(p, "n1")
        c.finish_binding("default/a", now=100.0)
        assert c.cleanup(now=129.0) == []          # not yet
        assert c.cleanup(now=130.0) == ["default/a"]
        assert c.get_pod("default/a") is None

    def test_unfinished_binding_never_expires(self):
        c = SchedulerCache(ttl=30.0)
        c.assume_pod(pod("a"), "n1")
        assert c.cleanup(now=1e9) == []  # no FinishBinding → no deadline

    def test_forget_pod(self):
        c = SchedulerCache()
        c.assume_pod(pod("a"), "n1")
        c.forget_pod("default/a")
        assert c.get_pod("default/a") is None
        # forgetting a bound pod is a lifecycle violation
        bound = pod("b")
        bound.node_name = "n1"
        c.add_pod(bound)
        with pytest.raises(CacheError):
            c.forget_pod("default/b")

    def test_double_assume_rejected(self):
        c = SchedulerCache()
        c.assume_pod(pod("a"), "n1")
        with pytest.raises(CacheError):
            c.assume_pod(pod("a"), "n2")

    def test_generation_moves_only_on_change(self):
        c = SchedulerCache()
        g0 = c.generation
        c.add_node(Node(name="n1"))
        g1 = c.generation
        assert g1 > g0
        c.cleanup(now=0.0)  # nothing expired → no bump
        assert c.generation == g1

    def test_snapshot_cached_until_generation_moves(self):
        c = SchedulerCache()
        c.add_node(Node(name="n1", allocatable=Resources.make(cpu=4, memory="8Gi")))
        enc = Encoder()
        pend = [pod("p1")]
        s1 = c.snapshot(enc, pend)
        s2 = c.snapshot(enc, pend)
        assert s1 is s2                       # no change → same object
        c.add_node(Node(name="n2", allocatable=Resources.make(cpu=4, memory="8Gi")))
        s3 = c.snapshot(enc, pend)
        assert s3 is not s2
        assert s3.node_order == ["n1", "n2"]

    def test_snapshot_recomputed_on_pending_change(self):
        c = SchedulerCache()
        c.add_node(Node(name="n1", allocatable=Resources.make(cpu=4, memory="8Gi")))
        enc = Encoder()
        s1 = c.snapshot(enc, [pod("p1")])
        s2 = c.snapshot(enc, [pod("p2")])
        assert s1 is not s2


class _WalkingCache:
    """The plain reference of the cache's assume bookkeeping: one dict of
    pod states, and every question (which assumed pods expired, how many
    are assumed) answered by a walk over ALL of it, as the cache did before
    it kept the assumed pods in an index of their own. Keys only: what a
    pod looks like is not the bookkeeping's business."""

    def __init__(self, ttl, n_nodes):
        self.ttl, self.n_nodes = ttl, n_nodes
        self.pods = {}   # key -> {"assumed", "finished", "deadline"}

    def assume_pod(self, key):
        if key in self.pods:
            raise CacheError(key)
        self.pods[key] = {"assumed": True, "finished": False,
                          "deadline": None}

    def finish_binding(self, key, now):
        st = self.pods.get(key)
        if st is not None and st["assumed"]:
            st["finished"], st["deadline"] = True, now + self.ttl

    def forget_pod(self, key):
        st = self.pods.get(key)
        if st is None:
            return
        if not st["assumed"]:
            raise CacheError(key)
        del self.pods[key]

    def add_pod(self, key):
        st = self.pods.get(key)
        if st is not None and not st["assumed"]:
            raise CacheError(key)
        self.pods[key] = {"assumed": False, "finished": False,
                          "deadline": None}

    def update_pod(self, key):
        st = self.pods.get(key)
        if st is None or st["assumed"]:
            raise CacheError(key)

    def remove_pod(self, key):
        if key not in self.pods:
            raise CacheError(key)
        del self.pods[key]

    def _drop(self, gone):
        keys = [k for k, st in list(self.pods.items())
                if st["assumed"] and gone(st)]
        for k in keys:
            del self.pods[k]
        return keys

    def forget_assumed(self):
        return self._drop(lambda st: True)

    def cleanup(self, now):
        return self._drop(lambda st: st["finished"]
                          and st["deadline"] is not None
                          and now >= st["deadline"])

    def is_assumed(self, key):
        st = self.pods.get(key)
        return bool(st and st["assumed"])

    def assumed(self):
        return sum(1 for st in self.pods.values() if st["assumed"])

    def counts(self):
        return self.n_nodes, len(self.pods), self.assumed()


class _NoWalk(dict):
    """A pod map that may be asked for a key and for its size, and not
    walked."""

    def _refuse(self, *a, **kw):
        raise AssertionError("the cache walked its whole pod map")

    items = values = keys = __iter__ = _refuse


class TestAssumedIndex:
    """The cache answers for its assumed pods from the index it keeps of
    them (cache.go's assumedPods), never from a walk of every pod."""

    OPS = {"assume_pod": 8, "finish_binding": 8, "forget_pod": 2,
           "add_pod": 6, "update_pod": 2, "remove_pod": 2,
           "forget_assumed": 0.3, "cleanup": 6}   # op: its weight in the draw

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
    def test_random_lifecycles_agree_with_the_full_walk(self, seed):
        import random

        rng = random.Random(seed)
        ttl, nodes = 30.0, ["n0", "n1", "n2"]
        c = SchedulerCache(ttl=ttl)
        for n in nodes:
            c.add_node(Node(name=n))
        ref = _WalkingCache(ttl, len(nodes))
        keys = [f"default/p{i}" for i in range(24)]
        now, expired_ever, forgotten_ever, assumed_most = 0.0, 0, 0, 0
        for step in range(800):
            op, = rng.choices(list(self.OPS), list(self.OPS.values()))
            name = f"p{rng.randrange(len(keys))}"
            key = f"default/{name}"
            now += rng.choice((0.0, 0.5, 2.0, 8.0))
            bound = pod(name)
            bound.node_name = rng.choice(nodes)
            call = {
                "assume_pod": (lambda: c.assume_pod(pod(name),
                                                    rng.choice(nodes)),
                               lambda: ref.assume_pod(key)),
                "finish_binding": (lambda: c.finish_binding(key, now),
                                   lambda: ref.finish_binding(key, now)),
                "forget_pod": (lambda: c.forget_pod(key),
                               lambda: ref.forget_pod(key)),
                "add_pod": (lambda: c.add_pod(bound),
                            lambda: ref.add_pod(key)),
                "update_pod": (lambda: c.update_pod(bound),
                               lambda: ref.update_pod(key)),
                "remove_pod": (lambda: c.remove_pod(key),
                               lambda: ref.remove_pod(key)),
                "forget_assumed": (
                    lambda: [p.key for p in c.forget_assumed()],
                    ref.forget_assumed),
                "cleanup": (lambda: c.cleanup(now),
                            lambda: ref.cleanup(now)),
            }[op]
            got = want = None
            examined = ref.assumed()
            assumed_most = max(assumed_most, examined)
            try:
                want = call[1]()
            except CacheError:
                with pytest.raises(CacheError):
                    call[0]()
            else:
                got = call[0]()
            where = f"seed {seed} step {step} {op} {key} now {now}"
            if op in ("cleanup", "forget_assumed"):
                assert got == want, where       # the keys, and their order
            if op == "cleanup":
                assert c.last_cleanup_examined == examined, where
                expired_ever += len(want)
            if op == "forget_assumed":
                forgotten_ever += len(want)
            assert c.counts() == ref.counts(), where
            assert [c.is_assumed(k) for k in keys] \
                == [ref.is_assumed(k) for k in keys], where
            assert c.drain_confirm_waits()[1] == ref.assumed(), where
            # the index is the assumed states of the pod map, in its order
            assert list(c._assumed.items()) == [
                (k, st) for k, st in c._pods.items() if st.assumed], where
        # the sequence reached what it is here to compare
        assert expired_ever >= 5 and forgotten_ever and assumed_most >= 5

    def test_nothing_asked_every_wave_walks_the_pod_map(self):
        from kubernetes_tpu.state.cache import FakeCache

        c = FakeCache(ttl=30.0)
        for i in range(6):
            bound = pod(f"b{i}")
            bound.node_name = "n1"
            c.add_pod(bound)
        for name in ("a0", "a1", "a2", "a3"):
            c.assume_pod(pod(name), "n1")
        c.finish_binding("default/a0", now=0.0)
        c.finish_binding("default/a2", now=50.0)
        c.finish_binding("default/a3", now=50.0)
        c._pods = _NoWalk(c._pods)
        with pytest.raises(AssertionError):
            list(c._pods.items())               # the guard guards
        assert c.counts() == (0, 10, 4)
        assert c.cleanup(now=29.0) == []
        assert c.cleanup(now=30.0) == ["default/a0"]
        assert c.last_cleanup_examined == 4
        assert c.counts() == (0, 9, 3)
        assert c.drain_confirm_waits()[1] == 3
        assert c.expire_all_assumed() == ["default/a2", "default/a3"]
        assert [p.key for p in c.forget_assumed()] == ["default/a1"]
        assert c.counts() == (0, 6, 0) and not c._assumed

    def test_the_server_reads_its_gauges_from_the_counts(self):
        from kubernetes_tpu.apiserver import APIServer
        from kubernetes_tpu.client import Client
        from kubernetes_tpu.models.workloads import make_nodes
        from kubernetes_tpu.sched import metrics as sched_metrics
        from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler
        from kubernetes_tpu.sched.server import SchedulerServer

        s = Scheduler(binder=RecordingBinder(), batch_size=64)
        for n in make_nodes(5):
            s.on_node_add(n)
        for i in range(7):
            bound = pod(f"b{i}")
            bound.node_name = "node-0"
            s.cache.add_pod(bound)
        for i in range(3):
            s.on_pod_add(pod(f"p{i}", creation=i))

        def listed(*a, **kw):
            raise AssertionError("a wave copied the cache to count it")

        s.cache.scheduled_pods = s.cache.nodes = listed
        srv = SchedulerServer(Client.local(APIServer()), scheduler=s)
        stats = srv.run_one_wave()
        assert srv.last_wave_error is None and stats.scheduled == 3
        assert sched_metrics.CACHE_SIZE.value(type="nodes") == 5
        assert sched_metrics.CACHE_SIZE.value(type="pods") == 10
        assert s.cache.counts() == (5, 10, 3)


class TestPriorityQueue:
    def test_pop_order_priority_then_creation(self):
        q = PriorityQueue()
        q.add(pod("low", priority=0, creation=0))
        q.add(pod("high", priority=10, creation=5))
        q.add(pod("mid-old", priority=5, creation=1))
        q.add(pod("mid-new", priority=5, creation=2))
        got = [p.name for p, _ in q.pop_batch(10)]
        assert got == ["high", "mid-old", "mid-new", "low"]

    def test_unschedulable_waits_for_move(self):
        q = PriorityQueue()
        q.add(pod("a"))
        (p, attempts), = q.pop_batch(1, now=0.0)
        q.add_unschedulable(p, attempts, now=0.0)
        assert q.lengths() == (0, 0, 1)
        q.pump(now=5.0)
        assert q.lengths() == (0, 0, 1)       # no event, still parked
        q.move_all_to_active(now=5.0)
        assert q.lengths() == (1, 0, 0)       # backoff (1s) already elapsed

    def test_move_respects_remaining_backoff(self):
        q = PriorityQueue()
        q.add(pod("a"))
        (p, attempts), = q.pop_batch(1, now=0.0)
        q.add_unschedulable(p, attempts, now=0.0)
        q.move_all_to_active(now=0.5)         # 1s backoff not yet elapsed
        assert q.lengths() == (0, 1, 0)
        q.pump(now=0.9)
        assert q.lengths() == (0, 1, 0)
        q.pump(now=1.1)
        assert q.lengths() == (1, 0, 0)

    def test_exponential_backoff_caps_at_max(self):
        q = PriorityQueue()
        assert q.backoff_duration(1) == INITIAL_BACKOFF
        assert q.backoff_duration(2) == 2.0
        assert q.backoff_duration(4) == 8.0
        assert q.backoff_duration(5) == MAX_BACKOFF   # 16 → cap
        assert q.backoff_duration(9) == MAX_BACKOFF
        # config-surface bounds (apis/config/types.go:96-101)
        q2 = PriorityQueue(initial_backoff=2.0, max_backoff=4.0)
        assert q2.backoff_duration(1) == 2.0
        assert q2.backoff_duration(3) == 4.0

    def test_unschedulable_flushed_after_interval(self):
        q = PriorityQueue()
        q.add(pod("a"))
        (p, attempts), = q.pop_batch(1, now=0.0)
        q.add_unschedulable(p, attempts, now=0.0)
        q.pump(now=UNSCHEDULABLE_FLUSH_INTERVAL - 1)
        assert q.lengths() == (0, 0, 1)
        q.pump(now=UNSCHEDULABLE_FLUSH_INTERVAL)
        assert q.lengths() == (1, 0, 0)

    def test_move_after_pop_sends_failure_to_backoff(self):
        """moveRequestCycle: event arrives while the pod is mid-cycle → its
        failure verdict is stale → backoffQ, not unschedulableQ."""
        q = PriorityQueue()
        q.add(pod("a"))
        (p, attempts), = q.pop_batch(1, now=0.0)
        cycle = q.current_cycle()
        q.move_all_to_active(now=0.0)          # event during scheduling
        q.add_unschedulable(p, attempts, now=0.0, cycle=cycle)
        assert q.lengths() == (0, 1, 0)

    def test_update_moves_unschedulable_to_active(self):
        q = PriorityQueue()
        q.add(pod("a"))
        (p, attempts), = q.pop_batch(1, now=0.0)
        q.add_unschedulable(p, attempts, now=0.0)
        q.update(p, now=1.0)
        assert q.lengths() == (1, 0, 0)

    def test_delete_and_nominated(self):
        q = PriorityQueue()
        q.add(pod("a"))
        q.add_nominated("default/a", "n3")
        assert q.nominated_node("default/a") == "n3"
        assert q.nominated_on("n3") == ["default/a"]
        q.delete("default/a")
        assert q.nominated_node("default/a") is None
        assert q.pop_batch(1) == []

    def test_duplicate_add_not_doubled(self):
        q = PriorityQueue()
        q.add(pod("a"))
        q.add(pod("a"))
        assert q.lengths()[0] == 1
        assert len(q.pop_batch(10)) == 1


class _NoHeapWalk(list):
    """An activeQ heap that may be pushed to and popped from (heapq works
    on it through the list's C slots), and not walked from Python."""

    def _refuse(self, *a, **kw):
        raise AssertionError("the queue walked its heap")

    __iter__ = __reversed__ = _refuse


class TestOldestActiveInstant:
    """`active_stats()`: since when the oldest entry now in activeQ has
    waited there, for the server loop's gathering window (ISSUE 47); a
    running minimum that starts over when activeQ empties, read in O(1)."""

    def _q(self):
        q = PriorityQueue()
        q._active = _NoHeapWalk()
        return q

    def test_add_keeps_the_least_and_an_emptied_queue_starts_over(self):
        q = self._q()
        assert q.active_stats() == (0, 0.0)
        q.add(pod("a"), now=10.0)
        q.add(pod("b"), now=9.5)      # its handler entered earlier
        q.add(pod("c"), now=11.0)
        assert q.active_stats() == (3, 9.5)
        assert len(q.pop_batch(10, now=12.0)) == 3
        assert q.active_stats() == (0, 0.0)
        q.add(pod("d"), now=12.5)
        assert q.active_stats() == (1, 12.5)

    def test_a_partial_pop_never_reads_late(self):
        q = self._q()
        q.add(pod("a", priority=5), now=1.0)
        q.add(pod("b"), now=2.0)
        assert [p.name for p, _ in q.pop_batch(1, now=3.0)] == ["a"]
        depth, since = q.active_stats()
        assert depth == 1 and since <= 2.0    # a bound from below

    def test_delete_of_the_last_entry_starts_over(self):
        q = self._q()
        q.add(pod("a"), now=1.0)
        q.add(pod("b"), now=2.0)
        q.delete("default/a")
        assert q.active_stats()[0] == 1 and q.active_stats()[1] <= 2.0
        q.delete("default/b")
        assert q.active_stats() == (0, 0.0)
        q.add(pod("c"), now=7.0)
        assert q.active_stats() == (1, 7.0)

    def test_update_counts_from_the_update(self):
        q = self._q()
        q.add(pod("a"), now=1.0)
        q.update(pod("a"), now=4.0)           # re-admitted alone: from 4.0
        assert q.active_stats() == (1, 4.0)
        q.add(pod("b"), now=3.0)
        assert q.active_stats() == (2, 3.0)

    def test_requeues_and_flushes_count_from_when_they_come_back(self):
        q = self._q()
        q.add(pod("a"), now=0.0)
        q.add(pod("b"), now=0.0)
        (a, n_a), (b, n_b) = q.pop_batch(2, now=0.0)
        q.add_prompt_retry(a, n_a, now=5.0)   # a preemptor with a node
        assert q.active_stats() == (1, 5.0)
        q.pop_batch(1, now=5.0)
        # b failed at 0.0; an event moves it to backoff, the pump's flush
        # brings it back at 1.5: it counts from 1.5, not from its failure
        q.add_unschedulable(b, n_b, now=0.0)
        q.move_all_to_active(now=0.5)
        assert q.lengths() == (0, 1, 0) and q.active_stats() == (0, 0.0)
        q.pump(now=1.5)
        assert q.active_stats() == (1, 1.5)
        q.pop_batch(1, now=1.5)
        # past its backoff when the event comes: straight back, from then
        q.add_unschedulable(b, 1, now=2.0)
        q.move_all_to_active(now=9.0)
        assert q.active_stats() == (1, 9.0)

    def test_the_micro_lane_and_recovery_keep_it_too(self):
        q = self._q()
        q.add(pod("a"), now=1.0)
        assert len(q.pop_micro(4, now=2.0)) == 1
        assert q.active_stats() == (0, 0.0)
        assert q.requeue_recovered(pod("r"), now=6.0) == "active"
        assert q.active_stats() == (1, 6.0)


class TestStormBackoffBoundaries:
    """ISSUE 9 satellite: backoff boundaries under storm requeues — the
    clamp must hold (not crash) at attempt counts a storm accumulates,
    and a pod requeued from the shed + prompt-retry paths in one tick
    must land in exactly ONE lane."""

    def test_backoff_clamps_at_max_for_large_attempts(self):
        q = PriorityQueue()
        # pre-fix, 2.0 ** (attempts - 1) raised OverflowError past ~1024
        for attempts in (64, 1025, 2000, 10**6, 2**31):
            assert q.backoff_duration(attempts) == MAX_BACKOFF
        assert q.backoff_duration(0) == INITIAL_BACKOFF
        assert q.backoff_duration(-5) == INITIAL_BACKOFF
        # the clamp also survives custom bounds
        q2 = PriorityQueue(initial_backoff=0.5, max_backoff=7.0)
        assert q2.backoff_duration(100000) == 7.0

    def test_huge_attempts_requeue_does_not_crash(self):
        q = PriorityQueue()
        q.add(pod("a"))
        (p, _attempts), = q.pop_batch(1, now=0.0)
        q.add_unschedulable(p, attempts=5000, now=0.0)
        q.move_all_to_active(now=0.1)     # serves remaining-backoff math
        assert q.lengths() == (0, 1, 0)   # parked at the 10s cap
        q.pump(now=0.1 + MAX_BACKOFF)
        assert q.lengths() == (1, 0, 0)

    def test_shed_then_prompt_retry_single_lane(self):
        """A pod parked by the shed path and requeued by prompt-retry in
        the same tick must be live in exactly one lane (active wins —
        prompt retry is a promotion, the deferred entry dies)."""
        q = PriorityQueue()
        q.add(pod("a"))
        (p, attempts), = q.pop_batch(1, now=0.0)
        assert q.park_deferred(p, attempts, now=0.0)
        assert q.depths()["deferred"] == 1
        q.add_prompt_retry(p, attempts, now=0.0)
        d = q.depths()
        assert (d["active"], d["backoff"], d["deferred"]) == (1, 0, 0)
        assert len(q.pop_batch(10)) == 1  # exactly one live entry

    def test_prompt_retry_then_shed_single_lane(self):
        """The reverse order: a pod already promoted to activeQ refuses
        the park (shedding it would demote a pod on its way to a wave)."""
        q = PriorityQueue()
        q.add(pod("a"))
        (p, attempts), = q.pop_batch(1, now=0.0)
        q.add_prompt_retry(p, attempts, now=0.0)
        assert not q.park_deferred(p, attempts, now=0.0)
        d = q.depths()
        assert (d["active"], d["deferred"]) == (1, 0)

    def test_deferred_release_and_safety_flush(self):
        from kubernetes_tpu.sched.queue import DEFERRED_FLUSH_INTERVAL

        q = PriorityQueue()
        q.add(pod("a"))
        q.add(pod("b"))
        batch = q.pop_batch(2, now=0.0)
        for p, attempts in batch:
            q.park_deferred(p, attempts, now=0.0)
        assert q.depths()["deferred"] == 2
        assert q.get_pod("default/a") is not None  # visible to replay
        assert q.release_deferred(now=1.0) == 2
        assert q.depths() == {"active": 2, "backoff": 0,
                              "unschedulable": 0, "deferred": 0}
        # safety flush: a parked pod outlives a wedged governor
        (p, attempts), *_ = q.pop_batch(2, now=1.0)
        q.park_deferred(p, attempts, now=1.0)
        q.pump(now=1.0 + DEFERRED_FLUSH_INTERVAL)
        assert q.depths()["deferred"] == 0
        assert q.lengths()[0] >= 1

    def test_deferred_delete_and_update(self):
        q = PriorityQueue()
        q.add(pod("a"))
        (p, attempts), = q.pop_batch(1, now=0.0)
        q.park_deferred(p, attempts, now=0.0)
        q.delete("default/a")                 # pod deleted while parked
        assert q.depths()["deferred"] == 0
        assert q.get_pod("default/a") is None
        q.add(pod("b"))
        (p2, a2), = q.pop_batch(1, now=0.0)
        q.park_deferred(p2, a2, now=0.0)
        q.update(p2, now=0.0)                 # spec change un-parks it
        d = q.depths()
        assert (d["active"], d["deferred"]) == (1, 0)
