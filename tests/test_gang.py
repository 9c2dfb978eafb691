"""Gang/co-scheduling (ops/gang.py + the Coscheduling Permit plugin).

The soundness bar mirrors tests/test_waves.py: beyond unit behavior, the
gang engine's output must (a) never commit a partial group — for every group,
placed ≥ needed or placed == 0 — and (b) remain a valid greedy execution of
the reference's per-pod loop when replayed through the pure-Python oracle.
"""

import dataclasses
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api.types import Node, Pod, PodGroup, Resources
from kubernetes_tpu.api.v1 import pod_from_v1, pod_to_v1
from kubernetes_tpu.ops.assign import initial_state
from kubernetes_tpu.ops.gang import assign_gang
from kubernetes_tpu.ops.lattice import build_cycle
from kubernetes_tpu.sched.cycle import UNSCHEDULABLE_TAINT_KEY, BatchScheduler
from kubernetes_tpu.state.encode import Encoder

from test_golden import oracle_fits, rand_node, rand_pod


def mknodes(n, cpu="4"):
    return [Node(name=f"n{i}",
                 allocatable=Resources.make(cpu=cpu, memory="8Gi", pods=110))
            for i in range(n)]


def gang_pods(prefix, count, group, min_member, cpu="1", priority=0, base=0):
    return [Pod(name=f"{prefix}{i}", requests=Resources.make(cpu=cpu),
                pod_group=group, min_member=min_member,
                priority=priority, creation_index=base + i)
            for i in range(count)]


class TestAllOrNothing:
    def test_feasible_group_places_fully(self):
        res = BatchScheduler().schedule(
            mknodes(4), [], gang_pods("a", 4, "jobA", 4))
        assert res.scheduled == 4 and res.failed == 0

    def test_infeasible_group_places_nothing(self):
        # 4 nodes × 4cpu; 6 members × 3cpu need 6 nodes — minMember 6 can
        # never fill, so NOT EVEN the 4 that would fit may commit
        res = BatchScheduler().schedule(
            mknodes(4), [], gang_pods("b", 6, "jobB", 6, cpu="3"))
        assert res.scheduled == 0 and res.failed == 6

    def test_min_member_below_count_allows_partial_above_min(self):
        # group of 6, minMember 4, capacity for exactly 4 (one 3cpu per node):
        # quorum is met → the 4 that fit commit, 2 stay pending
        res = BatchScheduler().schedule(
            mknodes(4), [], gang_pods("c", 6, "jobC", 4, cpu="3"))
        assert res.scheduled == 4 and res.failed == 2

    def test_ungrouped_pods_unaffected_by_rejections(self):
        pods = gang_pods("d", 6, "jobD", 6, cpu="3") + [
            Pod(name="solo", requests=Resources.make(cpu="1"),
                creation_index=50)]
        res = BatchScheduler().schedule(mknodes(4), [], pods)
        assert res.assignments[-1] is not None  # solo pod still placed
        assert all(a is None for a in res.assignments[:6])

    def test_bound_members_count_toward_quorum(self):
        # 2 members already bound; minMember 4; only 2 more can fit → the
        # pending pair commits because needed nets to 2
        nodes = mknodes(4)
        bound = [dataclasses.replace(p, node_name=f"n{i}")
                 for i, p in enumerate(
                     gang_pods("e", 2, "jobE", 4, cpu="3"))]
        res = BatchScheduler().schedule(
            nodes, bound, gang_pods("f", 2, "jobE", 4, cpu="3", base=10))
        assert res.scheduled == 2


class TestContention:
    def test_older_group_wins_resource_pocket(self):
        # 16 cpu total; two gangs each needing all 16 — naive half-split
        # underfills both; rejection order must fully place the older one
        gA = gang_pods("a", 8, "jobA", 8, cpu="2", base=0)
        gB = gang_pods("b", 8, "jobB", 8, cpu="2", base=100)
        res = BatchScheduler().schedule(mknodes(4), [], gA + gB)
        a = res.assignments
        assert all(x is not None for x in a[:8])
        assert all(x is None for x in a[8:])

    def test_higher_priority_group_wins(self):
        gA = gang_pods("a", 8, "jobA", 8, cpu="2", base=0)
        gC = gang_pods("c", 8, "jobC", 8, cpu="2", base=200, priority=100)
        res = BatchScheduler().schedule(mknodes(4), [], gA + gC)
        a = res.assignments
        assert all(x is None for x in a[:8])
        assert all(x is not None for x in a[8:])

    def test_three_way_contention_converges(self):
        # capacity for exactly one gang; three compete; exactly one fills
        gangs = [gang_pods(p, 8, f"job{p}", 8, cpu="2", base=i * 100)
                 for i, p in enumerate("xyz")]
        res = BatchScheduler().schedule(
            mknodes(4), [], [p for g in gangs for p in g])
        placed = [sum(a is not None for a in res.assignments[i*8:(i+1)*8])
                  for i in range(3)]
        assert sorted(placed) == [0, 0, 8]
        assert placed[0] == 8  # deterministic: the oldest


def _encode(nodes, existing, pending):
    enc = Encoder()
    enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
    enc.vocabs.label_vals.intern("")
    tables, ex, pe, d = enc.encode_cluster(nodes, existing, pending, None)
    uk = jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY))
    ev = jnp.int32(enc.vocabs.label_vals.get(""))
    gang = enc.build_gang_arrays(pending, d)
    return tables, ex, pe, gang, uk, ev, d


@functools.partial(jax.jit, static_argnums=(6,))
def _run_gang(tables, ex, pe, gang, uk, ev, D):
    cyc = build_cycle(tables, ex, uk, ev, D)
    init = initial_state(tables, cyc)
    return assign_gang(tables, cyc, pe, init, gang, return_waves=True)


@pytest.mark.parametrize("seed", range(6))
def test_gang_soundness_randomized(seed):
    """Randomized clusters with random gangs layered on adversarial pods:
    (a) no partial group ever commits; (b) the final assignment replays
    through the full oracle predicate chain in (wave, queue) order."""
    rng = random.Random(7000 + seed)
    n_nodes = rng.randint(4, 8)
    nodes = [rand_node(rng, i) for i in range(n_nodes)]
    existing = [rand_pod(rng, 100 + i, bound_to=rng.choice(nodes).name)
                for i in range(rng.randint(0, 4))]
    pending = [rand_pod(rng, i) for i in range(rng.randint(8, 14))]
    # group a random subset into 1-3 gangs with random minMember
    n_groups = rng.randint(1, 3)
    for i, p in enumerate(pending):
        if rng.random() < 0.6:
            g = rng.randrange(n_groups)
            pending[i] = dataclasses.replace(
                p, pod_group=f"g{g}", min_member=rng.randint(1, 4))

    tables, ex, pe, gang, uk, ev, d = _encode(nodes, existing, pending)
    if gang is None:
        pytest.skip("no gang pods drawn")
    res, dead, waves = _run_gang(tables, ex, pe, gang, uk, ev, d.D)
    node_idx = np.asarray(res.node)[: len(pending)]
    wave_idx = np.asarray(waves)[: len(pending)]

    # (a) all-or-nothing per group — keyed by NAMESPACED group (rand_pod
    # draws mixed namespaces; "ns1/g0" and "ns2/g0" are distinct gangs)
    enc_groups = {}
    for i, p in enumerate(pending):
        if p.pod_group:
            enc_groups.setdefault(f"{p.namespace}/{p.pod_group}", []).append(i)
    for gname, members in enc_groups.items():
        placed = sum(node_idx[i] >= 0 for i in members)
        needed = max(p.min_member for p in
                     (pending[i] for i in members))
        assert placed == 0 or placed >= needed, (
            f"seed={seed}: group {gname} committed {placed} members, "
            f"needed {needed} — partial commit")

    # (b) oracle replay in (wave, queue) order
    placed = sorted(
        (int(wave_idx[i]), -pending[i].priority, pending[i].creation_index, i)
        for i in range(len(pending)) if node_idx[i] >= 0)
    world = list(existing)
    for _, _, _, i in placed:
        node = nodes[int(node_idx[i])]
        assert oracle_fits(pending[i], node, nodes, world), (
            f"seed={seed}: gang-path pod {pending[i].name} on {node.name} "
            f"violates the oracle at replay")
        world.append(dataclasses.replace(pending[i], node_name=node.name))


def test_gang_workload_jobs_wholly_placed_or_wholly_not():
    """The gang workload (jobs of 8, 16, 32 and a 64-member monster that is
    statically infeasible on 12 nodes, which forces a rejection round)
    through the default engine: every job is wholly placed or wholly not;
    the three that fit are placed, the monster holds nothing."""
    from kubernetes_tpu.models.workloads import gang_workload_pods, make_nodes

    nodes = make_nodes(12, zones=3, racks_per_zone=2, cpu="16",
                       memory="64Gi")
    pods = gang_workload_pods(120)
    placed_of: dict = {}
    for p, node in zip(pods, BatchScheduler().schedule(
            nodes, [], pods).assignments):
        placed_of.setdefault(p.pod_group, []).append(node is not None)
    assert {g: (sum(v), len(v)) for g, v in placed_of.items()} == {
        "job-0": (8, 8), "job-1": (16, 16), "job-2": (32, 32),
        "job-3": (0, 64)}


class TestStatefulScheduler:
    def _mk(self):
        from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

        binder = RecordingBinder()
        s = Scheduler(binder=binder)
        return s, binder

    def test_gang_via_snapshot_path(self):
        s, binder = self._mk()
        for n in mknodes(4):
            s.on_node_add(n)
        for p in gang_pods("a", 4, "jobA", 4):
            s.on_pod_add(p)
        for p in gang_pods("b", 6, "jobB", 6, cpu="3", base=10):
            s.on_pod_add(p)
        stats = s.schedule_pending()
        assert stats.scheduled == 4
        assert stats.unschedulable == 6
        assert {k for k, _ in binder.bound} == {
            f"default/a{i}" for i in range(4)}

    def test_rejected_gang_retries_when_capacity_frees(self):
        from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

        now = [0.0]
        binder = RecordingBinder()
        s = Scheduler(binder=binder, clock=lambda: now[0])
        for n in mknodes(2):
            s.on_node_add(n)
        # occupy the cluster: group can't fill → rejected → queued
        blocker = [Pod(name=f"x{i}", requests=Resources.make(cpu="4"),
                       node_name=f"n{i}", creation_index=i)
                   for i in range(2)]
        for p in blocker:
            s.on_pod_add(p)
        for p in gang_pods("g", 2, "jobG", 2, cpu="3", base=10):
            s.on_pod_add(p)
        assert s.schedule_pending().scheduled == 0
        # free capacity; advance past the retry backoff; the flush retries
        for p in blocker:
            s.on_pod_delete(p)
        now[0] = 60.0
        stats = s.run_until_idle()
        assert len(binder.bound) == 2

    def test_gang_bound_counts_net_out_in_cache(self):
        s, binder = self._mk()
        for n in mknodes(4):
            s.on_node_add(n)
        # two members bound out-of-band count toward jobE's minMember 4
        for i, p in enumerate(gang_pods("e", 2, "jobE", 4, cpu="3")):
            s.on_pod_add(dataclasses.replace(p, node_name=f"n{i}"))
        for p in gang_pods("f", 2, "jobE", 4, cpu="3", base=10):
            s.on_pod_add(p)
        assert s.schedule_pending().scheduled == 2


class TestCoschedulingPermitPlugin:
    """The host per-pod path: Permit WAIT until quorum, then release
    (framework/plugins.py Coscheduling; waiting_pods_map semantics)."""

    def _mk(self, min_member=3, timeout=30.0):
        from kubernetes_tpu.framework.plugins import (
            default_framework, default_plugins,
        )
        from kubernetes_tpu.framework.runtime import PluginSet
        from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

        plugins = dataclasses.replace(
            default_plugins(),
            reserve=PluginSet(enabled=["Coscheduling"]),
            permit=PluginSet(enabled=["Coscheduling"]),
            unreserve=PluginSet(enabled=["Coscheduling"]),
        )
        fw = default_framework(plugins=plugins)
        binder = RecordingBinder()
        s = Scheduler(binder=binder, framework=fw, batch_size=1)
        cos = next(p for p in fw.permit_plugins if p.name == "Coscheduling")
        cos.on_release = s.complete_waiting
        cos.timeout = timeout
        return s, binder, cos

    def test_members_wait_then_release_on_quorum(self):
        s, binder, cos = self._mk()
        cos.register_group("default/jobP", 3)
        for n in mknodes(4):
            s.on_node_add(n)
        members = gang_pods("p", 3, "jobP", 3)
        # batch_size=1 → one member per wave: first two park in Permit WAIT
        s.on_pod_add(members[0])
        s.schedule_pending()
        assert len(binder.bound) == 0
        assert len(s.framework.waiting_pods()) == 1
        s.on_pod_add(members[1])
        s.schedule_pending()
        assert len(binder.bound) == 0
        assert len(s.framework.waiting_pods()) == 2
        # third member reaches quorum: releases both waiters + binds itself
        s.on_pod_add(members[2])
        s.schedule_pending()
        assert len(binder.bound) == 3
        assert len(s.framework.waiting_pods()) == 0

    def test_timeout_rejects_and_requeues_waiters(self):
        s, binder, cos = self._mk(timeout=5.0)
        cos.register_group("default/jobQ", 3)
        for n in mknodes(4):
            s.on_node_add(n)
        s.on_pod_add(gang_pods("q", 1, "jobQ", 3)[0])
        s.schedule_pending()
        assert len(s.framework.waiting_pods()) == 1
        # jump the clock past the permit deadline (relative to the framework
        # clock, which stamped the waiting deadline with time.monotonic())
        import time as _time

        base = _time.monotonic()
        s.clock = lambda: base + 10_000.0
        s.expire_waiting()
        assert len(s.framework.waiting_pods()) == 0
        assert len(binder.bound) == 0
        # the waiter was unreserved: the group's reserved set is empty again
        assert not cos._reserved.get("default/jobQ")


def test_gang_that_arrives_during_a_wave_binds_in_one_wave_after_it():
    """ISSUE 47: four members reach the scheduler over 6 ms while a wave
    holds the server's lock. The first stands at the lock from its entry,
    the others wait in the informer's buffer behind it; all are stamped
    with when they came. The loop then gives them what is left of the
    0.05 s window from the FIRST member's arrival, no new 0.05 s from its
    own peek, and ONE wave binds the gang whole."""
    import threading
    import types

    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler
    from kubernetes_tpu.sched.server import SchedulerServer
    from test_telemetry import _HeldByAWave

    clk = {"t": 100.0}
    binder = RecordingBinder()
    s = Scheduler(binder=binder, clock=lambda: clk["t"])
    s.telemetry.clock = lambda: clk["t"]
    for n in mknodes(4):
        s.on_node_add(n)
    srv = SchedulerServer(Client.local(APIServer()), scheduler=s,
                          cycle_interval=0.02, batch_window=0.15)
    came = [100.005, 100.007, 100.009, 100.011]   # the wave ends at 100.045
    now_handling = [0]
    srv.pod_informer = types.SimpleNamespace(
        buffered=lambda: 0, relists=0,
        delivery_lag=lambda: clk["t"] - came[now_handling[0]])

    srv._mu = _HeldByAWave(clk, free_at=100.045)
    clk["t"] = came[0]
    for i, p in enumerate(gang_pods("m", 4, "jobM", 4)):
        now_handling[0] = i
        srv._on_pod_add(pod_to_v1(p))
        clk["t"] += 0.0002              # a handler's turn
    srv._mu = threading.Lock()
    assert s.queue.active_stats() == (4, pytest.approx(100.005))
    naps = []

    class _Stop:
        def wait(self, timeout):
            naps.append(timeout)
            clk["t"] += timeout

    srv._stop = _Stop()
    srv._gather(s.telemetry.loop_lap)
    stats = srv.run_one_wave()
    assert stats is not None, srv.last_wave_error
    assert stats.attempted == 4 and stats.scheduled == 4   # ONE wave, whole
    assert naps == [pytest.approx(0.05 - (100.0458 - 100.005))]
    assert {k for k, _ in binder.bound} == {f"default/m{i}" for i in range(4)}


def test_group_ids_compact_on_full_snapshot():
    """Finished gang jobs must not grow GR forever: a full re-encode
    compacts dead group ids (the gang analog of domain-map compaction), so
    a long-running scheduler's GangArrays stay sized to LIVE groups."""
    from kubernetes_tpu.sched.cycle import snapshot_with_keys
    from kubernetes_tpu.state.cache import SchedulerCache

    cache = SchedulerCache()
    enc = Encoder()
    for n in mknodes(4):
        cache.add_node(n)
    # churn many short-lived gangs through the encoder
    for j in range(200):
        for p in gang_pods("w", 2, f"job-{j}", 2, base=j * 10):
            enc.group_id(p)
    assert len(enc.pod_groups) >= 200
    # a full snapshot with one live gang compacts the vocab to just it
    live = gang_pods("live", 2, "job-live", 2, base=9000)
    snap, _ = snapshot_with_keys(cache, enc, live, None)
    assert cache.last_snapshot_mode == "full"
    assert len(enc.pod_groups) == 1
    assert snap.dims.GR <= 4  # floor, not the churned 200+
    assert snap.gang is not None and int(snap.gang.valid.sum()) == 1


def test_podgroup_object_overrides_pod_hints():
    enc = Encoder()
    enc.set_group_min("default/jobZ", 7)
    p = Pod(name="z0", pod_group="jobZ", min_member=2)
    g = enc.group_id(p)
    assert enc.group_min[g] == 7  # authoritative PodGroup wins over the hint


def test_gang_annotations_round_trip_v1():
    p = Pod(name="w0", pod_group="trainers", min_member=16,
            requests=Resources.make(cpu="2"))
    back = pod_from_v1(pod_to_v1(p))
    assert back.pod_group == "trainers"
    assert back.min_member == 16
    # label-carried form parses too
    obj = pod_to_v1(p)
    obj["metadata"].pop("annotations")
    obj["metadata"]["labels"][
        "pod-group.scheduling.sigs.k8s.io/name"] = "trainers"
    assert pod_from_v1(obj).pod_group == "trainers"


def test_podgroup_object_key():
    g = PodGroup(name="train", namespace="ml", min_member=8)
    assert g.key == "ml/train"


# --------------------------------------------------------------------------- #
# the served path: Client.local -> apiserver -> SchedulerServer, with jobs that
# can and jobs that cannot reach their min-available (ISSUE 32); the pods, the
# wiring and the two checks are the benchmark's own (benchmarks/harness)
# --------------------------------------------------------------------------- #

SERVED_CFG = {
    "nodes": 64, "zones": 4, "racks_per_zone": 4, "node_cpu": "32000m",
    "node_memory": "134217728Ki", "node_pods": 110,
    "job_sizes": [4, 8], "request_tiers": [["1000m", "2097152Ki"],
                                          ["4000m", "8388608Ki"]],
    "job_priorities": [0, 1, 2], "complete_jobs_per_shape": 3,
    "incomplete_jobs_per_shape": 2, "incomplete_members_share": 0.75,
    "oversized_jobs_per_size": 1, "oversized_request": ["64000m", "1Gi"],
    "backlog_pods": 72, "waiting_pods": 48, "batch_pods": 256,
    "existing_capacity_pods": 512, "dims": {"GR": 32},
    "preemption": True, "bind_intent_ledger": True,
    "assumed": {"cycle_interval_s": 0.02, "batch_window_s": 0.05},
}


def _serve(seed, strip_groups=False):
    """One served run over SERVED_CFG's jobs: every pod is at the apiserver
    when the scheduler starts; then one plain pod on its own. Returns what
    the apiserver lists, the nodes, the wave records and the Events."""
    from benchmarks.harness.probes import wait_until
    from benchmarks.harness.shapes import gang_jobs
    from benchmarks.harness.wirings import local

    pop = gang_jobs.Population(SERVED_CFG, seed, 0)
    must = pop.pending(SERVED_CFG["backlog_pods"], seed, "job")
    must_not = pop.waiting(seed, "job")
    pods = must + must_not
    if strip_groups:
        pods = [{**p, "metadata": {**p["metadata"], "annotations": {}}}
                for p in pods]
    cluster = local.Cluster(SERVED_CFG)
    try:
        client = cluster.client
        nodes = gang_jobs.make_nodes(SERVED_CFG)
        for o in nodes:
            client.nodes.create(o)
        for o in pods:
            client.pods.create(o)
        server = cluster.new_server()
        server.start()

        def bound(names):
            items = client.pods.list("default")["items"]
            return sum(1 for p in items if p["metadata"]["name"] in names
                       and p["spec"].get("nodeName"))

        want = {p["metadata"]["name"] for p in must}
        assert wait_until(lambda: bound(want) == len(want), 60), \
            (bound(want), server.last_wave_error)
        # the refused gangs' Events leave the wave AFTER its Bindings (the
        # loop queues them once the commit is through, the sink thread
        # writes them): wait for what the tests below read, one
        # FailedScheduling Event a refused member, not for the Bindings alone
        refused = {p["metadata"]["name"] for p in must_not}

        def told():
            server.recorder.flush(10)
            return strip_groups or refused <= {
                e["involvedObject"]["name"]
                for e in client.events.list("default")["items"]
                if e["reason"] == "FailedScheduling"}

        assert wait_until(told, 60), "the refused gangs' Events"
        listing = client.pods.list("default")["items"]
        events = client.events.list("default")["items"]
        # a wave of its own for a pod of no group: the refused jobs leave
        for p in must_not:
            client.pods.delete(p["metadata"]["name"], "default")
        assert wait_until(lambda: not any(
            server.scheduler.queue.depths().values()), 60)
        plain = {**must[0], "metadata": {
            "name": "plain-0", "namespace": "default",
            "uid": "default/plain-0", "labels": {"app": "plain"}}}
        client.pods.create(plain)
        assert wait_until(lambda: bound({"plain-0"}) == 1, 60)
        records = [r for r in server.scheduler.telemetry.recorder.records()
                   if (r.get("stats") or {}).get("attempted")]
        return {"listing": listing, "nodes": nodes, "must": want,
                "must_not": {p["metadata"]["name"] for p in must_not},
                "sent": {p["metadata"]["name"]: p for p in must + must_not},
                "records": records, "events": events}
    finally:
        cluster.close()


@pytest.fixture(scope="module", params=[3, 2 ** 31 + 11])
def served(request):
    return _serve(request.param)


def _bound_names(run):
    return {p["metadata"]["name"] for p in run["listing"]
            if p["spec"].get("nodeName")}


def test_served_complete_jobs_wholly_bound_the_others_wholly_unbound(served):
    got = _bound_names(served)
    assert served["must"] <= got
    assert not served["must_not"] & got


def test_served_run_passes_the_benchmarks_checks(served):
    from benchmarks.harness.checks import gangs, placement

    ctx = {"cfg": SERVED_CFG, "check_spread": True}
    assert gangs.final_state(served["nodes"], served["listing"], ctx) == []
    assert placement.final_state(served["nodes"], served["listing"],
                                 ctx) == []


def test_served_wave_record_carries_the_gang_loops_verdict(served):
    first = served["records"][0]
    # 8 incomplete + 2 oversized jobs among 22: the first fixpoint, then
    # rejection rounds
    assert first["gang_groups"] == 22
    assert first["gang_groups_rejected"] == 10
    assert first["gang_rounds"] >= 2
    assert any(path.endswith("/gang") and path.startswith("snapshot/")
               for path in first["children"])


def test_served_gang_free_wave_carries_no_gang_field(served):
    last = served["records"][-1]
    assert last["stats"]["attempted"] == 1
    assert not [k for k in last if k.startswith("gang")]
    assert not any(path.endswith("/gang") for path in last["children"])


def test_served_refused_gangs_events_name_the_group_and_why(served):
    said = {}
    for ev in served["events"]:
        if ev["reason"] == "FailedScheduling":
            said[ev["involvedObject"]["name"]] = ev["message"]
    assert set(said) >= served["must_not"]
    by_name = {p["metadata"]["name"]: p for p in served["listing"]}
    pending = needed = 0
    for name in served["must_not"]:
        job = by_name[name]["metadata"]["labels"]["app"]
        assert f"pod group default/{job}:" in said[name]
        assert "none placed" in said[name]
        pending += "are pending" in said[name]
        needed += "fit a node" in said[name]
    # incomplete jobs lack members; no member of an oversized job fits
    assert pending == 36 and needed == 12
    assert all("0 of the" in said[n] for n in served["must_not"]
               if "fit a node" in said[n])


def test_served_gang_counters_count_rounds_and_groups():
    from kubernetes_tpu.sched.metrics import GANG_GROUPS, GANG_ROUNDS

    rounds0 = GANG_ROUNDS.total()
    rejected0 = GANG_GROUPS.value(result="rejected")
    admitted0 = GANG_GROUPS.value(result="admitted")
    run = _serve(5)
    gang_waves = [r for r in run["records"] if "gang_rounds" in r]
    assert GANG_ROUNDS.total() - rounds0 == sum(
        r["gang_rounds"] for r in gang_waves) >= 2
    assert GANG_GROUPS.value(result="rejected") - rejected0 == sum(
        r["gang_groups_rejected"] for r in gang_waves) >= 10
    assert GANG_GROUPS.value(result="admitted") - admitted0 >= 12


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 11])
def test_served_without_the_annotations_jobs_are_partly_bound(seed):
    from benchmarks.harness.checks import gangs

    run = _serve(seed, strip_groups=True)
    assert all("gang_rounds" not in r for r in run["records"])
    # the scheduler saw no group, so the incomplete jobs' members are
    # bound; the check reads the groups off the pods as they were meant
    sent = run["sent"]
    listing = [{**p, "metadata": sent[p["metadata"]["name"]]["metadata"]}
               for p in run["listing"] if p["metadata"]["name"] in sent]
    partly = gangs.final_state(run["nodes"], listing, {})
    assert len(partly) == 8   # every incomplete job
