"""Extender boundary tests, modeled on the reference's ladder: in-process
backend calls first (FakeExtender style, core/extender_test.go:122-143), then
real HTTP servers on ephemeral ports (integration extender_test.go:290-312
httptest.NewServer analog), exercised through the HTTPExtender client."""

import json
import urllib.request

import pytest

from kubernetes_tpu.api.types import (
    Affinity,
    LabelSelector,
    Node,
    Pod,
    PodAffinityTerm,
    Requirement,
    Resources,
    Op,
    Taint,
    TaintEffect,
    Toleration,
    TolerationOp,
    VolumeRef,
)
from kubernetes_tpu.api.v1 import node_from_v1, node_to_v1, pod_from_v1, pod_to_v1
from kubernetes_tpu.extender import (
    ExtenderArgs,
    ExtenderBackend,
    ExtenderBindingArgs,
    ExtenderConfig,
    ExtenderServer,
    HTTPExtender,
)


def mknode(name, cpu=4, mem="8Gi", labels=None, **kw):
    return Node(name=name, labels=labels or {},
                allocatable=Resources.make(cpu=cpu, memory=mem, pods=110), **kw)


def mkpod(name, cpu="500m", mem="256Mi", **kw):
    return Pod(name=name, requests=Resources.make(cpu=cpu, memory=mem), **kw)


# --------------------------------------------------------------------------- #
# v1 JSON round-trip
# --------------------------------------------------------------------------- #


def test_v1_pod_roundtrip():
    pod = Pod(
        name="web-0", namespace="prod", uid="u-123",
        labels={"app": "web", "tier": "fe"},
        requests=Resources.make(cpu="1500m", memory="2Gi"),
        node_selector={"disktype": "ssd"},
        affinity=Affinity(
            anti_required=(PodAffinityTerm(
                selector=LabelSelector.of({"app": "web"}),
                topology_key="kubernetes.io/hostname"),),
        ),
        tolerations=(Toleration(key="gpu", op=TolerationOp.EXISTS,
                                effect=TaintEffect.NO_SCHEDULE),),
        priority=100,
    )
    rt = pod_from_v1(pod_to_v1(pod))
    assert rt.key == pod.key and rt.uid == "u-123"
    assert rt.requests.milli_cpu == 1500
    assert rt.requests.memory_kib == 2 * 1024 * 1024
    assert rt.node_selector == {"disktype": "ssd"}
    assert rt.affinity.anti_required[0].topology_key == "kubernetes.io/hostname"
    assert rt.tolerations[0].op == TolerationOp.EXISTS
    assert rt.priority == 100


def test_v1_pod_init_container_max_rule():
    """GetResourceRequest (predicates.go:763): Σ containers, max initContainers."""
    obj = {
        "metadata": {"name": "p", "namespace": "d"},
        "spec": {
            "containers": [
                {"name": "a", "resources": {"requests": {"cpu": "200m", "memory": "100Mi"}}},
                {"name": "b", "resources": {"requests": {"cpu": "300m", "memory": "100Mi"}}},
            ],
            "initContainers": [
                {"name": "init", "resources": {"requests": {"cpu": "1", "memory": "50Mi"}}},
            ],
        },
    }
    pod = pod_from_v1(obj)
    assert pod.requests.milli_cpu == 1000  # max(200+300, 1000)
    assert pod.requests.memory_kib == 200 * 1024  # max(100+100, 50) Mi


def test_v1_node_roundtrip():
    n = Node(name="n0", labels={"zone": "a"},
             allocatable=Resources.make(cpu=8, memory="16Gi", pods=110),
             taints=(Taint(key="dedicated", value="ml",
                           effect=TaintEffect.NO_SCHEDULE),),
             unschedulable=True)
    rt = node_from_v1(node_to_v1(n))
    assert rt.name == "n0" and rt.labels == {"zone": "a"}
    assert rt.allocatable.milli_cpu == 8000
    assert rt.taints[0].key == "dedicated"
    assert rt.unschedulable


# --------------------------------------------------------------------------- #
# in-process backend (FakeExtender rung)
# --------------------------------------------------------------------------- #


def _backend_with_cluster():
    be = ExtenderBackend()
    be.sync_nodes([
        mknode("big", cpu=8),
        mknode("small", cpu=1),
        mknode("tainted", cpu=8,
               taints=(Taint(key="dedicated", value="x",
                             effect=TaintEffect.NO_SCHEDULE),)),
    ])
    return be


def test_backend_filter_cache_capable():
    be = _backend_with_cluster()
    args = ExtenderArgs(
        pod=pod_to_v1(mkpod("p", cpu="2")),
        node_names=["big", "small", "tainted", "ghost"],
    )
    res = be.filter(args)
    assert res.node_names == ["big"]
    assert "small" in res.failed_nodes and "Insufficient" in res.failed_nodes["small"]
    assert "taint" in res.failed_nodes["tainted"]
    assert res.failed_nodes["ghost"] == "node not found in extender cache"


def test_backend_filter_full_nodes_mode():
    """nodeCacheCapable=false: full v1.Node objects in, subset out."""
    be = ExtenderBackend()
    args = ExtenderArgs(
        pod=pod_to_v1(mkpod("p", cpu="2")),
        nodes=[node_to_v1(mknode("a", cpu=8)), node_to_v1(mknode("b", cpu=1))],
    )
    res = be.filter(args)
    assert [n["metadata"]["name"] for n in res.nodes] == ["a"]
    assert "b" in res.failed_nodes


def test_backend_prioritize_prefers_empty_node():
    be = ExtenderBackend()
    be.sync_nodes([mknode("empty", cpu=8), mknode("busy", cpu=8)])
    busy_pod = mkpod("occupant", cpu="6")
    busy_pod.node_name = "busy"
    be.sync_scheduled_pods([busy_pod])
    prios = be.prioritize(ExtenderArgs(
        pod=pod_to_v1(mkpod("p", cpu="1")), node_names=["empty", "busy"]))
    scores = {p.host: p.score for p in prios}
    assert scores["empty"] > scores["busy"]
    assert 0 <= scores["busy"] <= 10 and scores["empty"] <= 10


def _spread_verbs(role):
    """`filter` then `prioritize` for one pod under topology spread, on nine
    nodes in three zones (one without the zone label) holding `web` pods
    unevenly: (passed names, failed names, {host: score})."""
    from kubernetes_tpu.api.types import (LabelSelector,
                                          TopologySpreadConstraint,
                                          UnsatisfiableAction)

    zone, host = "topology.kubernetes.io/zone", "kubernetes.io/hostname"
    be = ExtenderBackend()
    nodes = []
    for i in range(9):
        labels = {host: f"n{i}", "pool": "ab"[i % 2]}
        if i != 4:
            labels[zone] = f"z{i % 3}"
        nodes.append(mknode(f"n{i}", labels=labels))
    be.sync_nodes(nodes)
    bound = []
    for i in range(9):
        for j in range(1 + 2 * (i % 3 == 0) + (i == 1)):
            p = mkpod(f"web-{i}-{j}", cpu="100m", labels={"app": "web"})
            p.node_name = f"n{i}"
            bound.append(p)
    be.sync_scheduled_pods(bound)
    sel = LabelSelector.of(match_labels={"app": "web"})
    hard, soft = (UnsatisfiableAction.DO_NOT_SCHEDULE,
                  UnsatisfiableAction.SCHEDULE_ANYWAY)
    spread = {
        "hard-zone-soft-hostname": (
            TopologySpreadConstraint(1, zone, hard, sel),
            TopologySpreadConstraint(1, host, soft, sel)),
        "hard-hostname-soft-zone": (
            TopologySpreadConstraint(1, host, hard, sel),
            TopologySpreadConstraint(1, zone, soft, sel)),
        "soft-zone-behind-a-selector": (
            TopologySpreadConstraint(1, zone, soft, sel),),
    }[role]
    pod = mkpod("p", cpu="100m", labels={"app": "web"},
                topology_spread=spread,
                node_selector={"pool": "a"} if "selector" in role else {})
    names = [n.name for n in nodes]
    res = be.filter(ExtenderArgs(pod=pod_to_v1(pod), node_names=names))
    prios = be.prioritize(ExtenderArgs(pod=pod_to_v1(pod),
                                       node_names=list(res.node_names)))
    return (list(res.node_names), dict(res.failed_nodes),
            {p.host: p.score for p in prios})


@pytest.mark.parametrize("role", ["hard-zone-soft-hostname",
                                  "hard-hostname-soft-zone",
                                  "soft-zone-behind-a-selector"])
def test_verbs_with_product_equal_verbs_with_scatter(role, monkeypatch):
    """A verb's mask (the names `filter` passes, and the reasons of those it
    fails) and scores (`prioritize`) for a pod under hard and soft topology
    spread, from programs whose Dims choose the product against the same
    programs made to fall back to the scatter form (ISSUE 43: the spread
    counts ride the cycle's same-domain matrices too)."""
    import jax
    from kubernetes_tpu.state import dims as dims_mod

    product = _spread_verbs(role)
    monkeypatch.setattr(dims_mod, "DOMAIN_SUM_MAX_BYTES", 0)
    jax.clear_caches()
    try:
        scatter = _spread_verbs(role)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert product == scatter
    passed, failed, scores = product
    assert passed and len(set(scores.values())) > 1
    if role.startswith("hard"):
        # the zone-less node fails a zone constraint; a crowded domain fails
        assert failed and len(passed) + len(failed) == 9
        if role.startswith("hard-zone"):
            assert "n4" in failed
    else:
        assert set(passed) == {"n0", "n2", "n4", "n6", "n8"}


def test_backend_preemption_verifies_victims():
    be = ExtenderBackend()
    be.sync_nodes([mknode("n0", cpu=2)])
    victim = mkpod("victim", cpu="1500m")
    victim.node_name = "n0"
    be.sync_scheduled_pods([victim])

    from kubernetes_tpu.extender.wire import ExtenderPreemptionArgs, Victims

    # removing the victim makes room → node survives with the victim set
    args = ExtenderPreemptionArgs(
        pod=pod_to_v1(mkpod("p", cpu="1")),
        node_name_to_victims={"n0": Victims(pods=[pod_to_v1(victim)])},
    )
    res = be.process_preemption(args)
    assert "n0" in res.node_name_to_meta_victims

    # empty victim set but the pod doesn't fit → node dropped
    args2 = ExtenderPreemptionArgs(
        pod=pod_to_v1(mkpod("p2", cpu="1")),
        node_name_to_victims={"n0": Victims(pods=[])},
    )
    res2 = be.process_preemption(args2)
    assert "n0" not in res2.node_name_to_meta_victims


# --------------------------------------------------------------------------- #
# one evaluation a pod (ISSUE 52): `filter` snapshots and dispatches once and
# both verbs' answers are cut from its arrays. The three programs and the
# name-at-a-time loops the verbs used to run live on here as the reference
# --------------------------------------------------------------------------- #


def _reference_verbs(be, pod, filter_names, prioritize_names):
    """`filter`'s (NodeNames, FailedNodes) and `prioritize`'s [(Host, Score)]
    for `pod` over the mirror as it stands, by `_feasible`, `_diagnose` and
    `_scores` on one snapshot and a Python loop over the candidates."""
    import jax
    from kubernetes_tpu.extender.backend import _REASONS
    from kubernetes_tpu.extender.wire import MAX_EXTENDER_PRIORITY
    from kubernetes_tpu.sched.cycle import (_diagnose, _feasible, _scores,
                                            snapshot_with_keys)

    snap, keys = snapshot_with_keys(be.cache, be.encoder, [pod], be.base_dims)
    call = (snap.tables, snap.pending, keys, snap.dims.D, snap.existing)
    mask = jax.device_get(_feasible(*call))[0]
    comp = jax.device_get(_diagnose(*call))
    raw = jax.device_get(_scores(*call))[0]
    index = {name: i for i, name in enumerate(snap.node_order)}

    passing, failed = [], {}
    for name in filter_names:
        i = index.get(name)
        if i is not None and bool(mask[i]):
            passing.append(name)
        elif i is None:
            failed[name] = "node not found in extender cache"
        else:
            reasons = [_REASONS[j] for j, part in enumerate(comp)
                       if not bool(part[0][i])]
            failed[name] = "; ".join(reasons) or "node is not feasible"

    vals = []
    for name in prioritize_names:
        i = index.get(name)
        vals.append((name, float(raw[i]) if i is not None
                     else float("-inf")))
    finite = [s for _, s in vals if s != float("-inf")]
    hi = max(finite) if finite else 0.0
    lo = min(finite) if finite else 0.0
    span = (hi - lo) or 1.0
    prios = [(name, 0 if s == float("-inf")
              else round((s - lo) / span * MAX_EXTENDER_PRIORITY))
             for name, s in vals]
    return passing, failed, prios


ZONE, HOST = "topology.kubernetes.io/zone", "kubernetes.io/hostname"


def _small_mirror_nodes():
    """Nine nodes in three zones (n4 has no zone label), pools a/b; n7 is
    small and n8 tainted."""
    nodes = []
    for i in range(9):
        labels = {HOST: f"n{i}", "pool": "ab"[i % 2]}
        if i != 4:
            labels[ZONE] = f"z{i % 3}"
        nodes.append(mknode(
            f"n{i}", cpu=1 if i == 7 else 4, labels=labels,
            taints=(Taint(key="dedicated", value="x",
                          effect=TaintEffect.NO_SCHEDULE),) if i == 8
            else ()))
    return nodes


def _small_mirror(**kw):
    """The nodes above holding `web` pods unevenly over the zones and a `db`
    pod on n1, n2 and n5."""
    be = ExtenderBackend(**kw)
    be.sync_nodes(_small_mirror_nodes())
    bound = []
    for i in range(9):
        for j in range(1 + 2 * (i % 3 == 0) + (i == 1)):
            bound.append(mkpod(f"web-{i}-{j}", cpu="100m",
                               labels={"app": "web"}, node_name=f"n{i}"))
    for i in (1, 2, 5):
        bound.append(mkpod(f"db-{i}", cpu="200m", labels={"app": "db"},
                           node_name=f"n{i}"))
    bound.append(mkpod("writer", cpu="100m", node_name="n6", volumes=(
        VolumeRef(vol_id="shared", driver="kubernetes.io/gce-pd"),)))
    be.sync_scheduled_pods(bound)
    return be


def _asking_pod(case):
    from kubernetes_tpu.api.types import (TopologySpreadConstraint,
                                          UnsatisfiableAction)

    if case == "anti":
        return mkpod("asks", cpu="100m", uid="u-asks", labels={"app": "db"},
                     affinity=Affinity(anti_required=(PodAffinityTerm(
                         selector=LabelSelector.of({"app": "db"}),
                         topology_key=HOST),)))
    if case == "spread":
        return mkpod("asks", cpu="100m", uid="u-asks", labels={"app": "web"},
                     topology_spread=(TopologySpreadConstraint(
                         1, ZONE, UnsatisfiableAction.DO_NOT_SCHEDULE,
                         LabelSelector.of(match_labels={"app": "web"})),))
    if case == "several-reasons":
        return mkpod("asks", cpu="3", uid="u-asks",
                     node_selector={"pool": "a"})
    if case == "volume-conflict":    # as the wire carries it (`_asking_v1`)
        return mkpod("asks", cpu="100m", uid="u-asks")
    return mkpod("asks", cpu="100m", uid="u-asks")


def _asking_v1(case):
    v1 = pod_to_v1(_asking_pod(case))
    if case == "volume-conflict":
        v1["spec"]["volumes"] = [
            {"name": "data", "gcePersistentDisk": {"pdName": "shared"}}]
    return v1


NAMES = [f"n{i}" for i in range(9)]


@pytest.mark.parametrize("case", ["plain", "anti", "spread",
                                  "several-reasons", "volume-conflict",
                                  "unknown-name",
                                  "subset-out-of-order", "nodes-form"])
def test_answers_equal_the_three_programs_and_the_loops(case):
    be = _small_mirror()
    v1 = _asking_v1(case)
    pod = pod_from_v1(v1)
    names = {"unknown-name": NAMES[:4] + ["ghost"] + NAMES[4:],
             "subset-out-of-order": ["n6", "n2", "n8", "n0", "n2"],
             }.get(case, NAMES)
    if case == "nodes-form":
        # nodeCacheCapable=false: the caller's node objects, n3 as the
        # caller sees it (cordoned: not what the mirror held)
        objs = [node_to_v1(n) for n in _small_mirror_nodes()]
        objs[3]["spec"]["unschedulable"] = True
        flt = be.filter(ExtenderArgs(pod=v1, nodes=objs))
        passed = [n["metadata"]["name"] for n in flt.nodes]
        prio = be.prioritize(ExtenderArgs(
            pod=v1,
            nodes=[o for o in objs if o["metadata"]["name"] in passed]))
        assert flt.node_names is None and "n3" in flt.failed_nodes
    else:
        flt = be.filter(ExtenderArgs(pod=v1, node_names=names))
        passed = flt.node_names
        prio = be.prioritize(ExtenderArgs(pod=v1,
                                          node_names=list(passed)))
    want_pass, want_failed, want_prio = _reference_verbs(
        be, pod, names, list(passed))
    assert passed == want_pass and passed
    assert flt.failed_nodes == want_failed
    assert list(flt.failed_nodes) == list(want_failed)   # and in that order
    assert [(h.host, h.score) for h in prio] == want_prio
    assert prio.to_json() == [{"Host": h, "Score": s} for h, s in want_prio]
    assert prio.encode() == json.dumps(prio.to_json()).encode()
    assert all(type(s) is int for _h, s in want_prio)
    if case in ("anti", "spread", "several-reasons", "volume-conflict"):
        assert want_failed
    if case == "volume-conflict":
        # the ninth component had no text before ISSUE 52: the verb raised
        assert want_failed["n6"] == (
            "node(s) had volume conflicts or exceeded volume limits")
    if case == "several-reasons":
        assert want_failed["n7"] == (
            "node(s) didn't match node selector; Insufficient resources")
    if case == "unknown-name":
        assert want_failed["ghost"] == "node not found in extender cache"
    # over every name, the unknown one too, `prioritize` gives -inf a 0
    _p, _f, want_all = _reference_verbs(be, pod, [], names)
    again = be.prioritize(ExtenderArgs(pod=v1, node_names=names))
    assert [(h.host, h.score) for h in again] == want_all
    assert len({s for _h, s in want_all}) > 1


def test_scores_round_half_to_even_as_the_loop_did():
    """Raw scores that land on x.5 after scaling: `np.rint` in float64
    against `round` over Python floats."""
    import numpy as np
    from kubernetes_tpu.extender.backend import _Evaluation

    be = _small_mirror()
    raw = np.full(16, -np.inf, np.float32)
    raw[:9] = [0.0, 0.5, 1.5, 2.5, 3.5, 10.0, 6.5, 0.1, 9.5]
    be._kept = _Evaluation("u-asks", be._epoch, NAMES, np.ones(16, bool),
                           np.zeros(16, np.int32), raw)
    prio = be.prioritize(ExtenderArgs(pod=pod_to_v1(_asking_pod("plain")),
                                      node_names=NAMES + ["ghost"]))
    assert prio.scores == [round(float(s)) for s in raw[:9]] + [0]
    assert prio.scores[:5] == [0, 0, 2, 2, 4]


def test_the_epoch_counts_every_change_from_many_threads():
    """The informers' threads feed the mirror without the verbs' lock: with
    more threads than cores and a tiny switch interval no change is made
    without its count, while a verb thread keeps evaluating."""
    import sys
    import threading

    be = _small_mirror()
    epoch, threads, each = be._epoch, 16, 50
    pod = pod_to_v1(_asking_pod("plain"))
    stop = threading.Event()

    def feed(t):
        for i in range(each):
            if i % 2:
                be.observe_node(mknode(f"extra-{t}", cpu=1 + i % 3))
            else:
                be.observe_pod(mkpod(f"foreign-{t}-{i}", cpu="10m",
                                     node_name=f"n{t % 9}"))

    def ask():
        while not stop.is_set():
            be.filter(ExtenderArgs(pod=pod, node_names=NAMES))
            be.prioritize(ExtenderArgs(pod=pod, node_names=NAMES))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        asker = threading.Thread(target=ask)
        feeders = [threading.Thread(target=feed, args=(t,))
                   for t in range(threads)]
        for th in [asker] + feeders:
            th.start()
        for th in feeders:
            th.join(timeout=60)
        stop.set()
        asker.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not asker.is_alive() and not any(f.is_alive() for f in feeders)
    assert be._epoch - epoch == threads * each
    # and the mirror the verbs see at rest is the fed one
    _p, _f, want = _reference_verbs(be, pod_from_v1(pod), [], NAMES)
    prio = be.prioritize(ExtenderArgs(pod=pod, node_names=NAMES))
    assert [(h.host, h.score) for h in prio] == want


def test_a_priority_list_encodes_itself_as_json_dumps_would():
    from kubernetes_tpu.extender import HostPriorityList

    for hosts, scores in ([], []), (["n0"], [10]), (
            ['quo"te', "back\\slash", "nö-ascii", "tab\there", "n1"],
            [0, 3, 10, 7, 250]):
        prio = HostPriorityList(hosts, scores)
        assert prio.encode() == json.dumps(prio.to_json()).encode()
        assert [(h.host, h.score) for h in prio] == list(zip(hosts, scores))
        assert len(prio) == len(hosts)


def _counting(be, monkeypatch):
    """[snapshots taken] of the backend's mirror, whatever its telemetry."""
    taken, real = [], be.cache.snapshot

    def snapshot(*a, **kw):
        taken.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(be.cache, "snapshot", snapshot)
    return taken


def _evaluations():
    from kubernetes_tpu.extender.backend import EXTENDER_EVALUATIONS as ev

    return ev.value(result="computed"), ev.value(result="reused")


@pytest.mark.parametrize("telemetry", ["on", "off"])
def test_prioritize_after_filter_runs_no_program_and_no_snapshot(
        telemetry, monkeypatch):
    if telemetry == "off":
        monkeypatch.setenv("KTPU_TELEMETRY", "0")
    be = _small_mirror()
    taken, before = _counting(be, monkeypatch), _evaluations()
    pod = pod_to_v1(_asking_pod("anti"))
    flt = be.filter(ExtenderArgs(pod=pod, node_names=NAMES))
    assert len(taken) == 1
    be.prioritize(ExtenderArgs(pod=pod, node_names=flt.node_names))
    assert len(taken) == 1
    computed, reused = _evaluations()
    assert (computed - before[0], reused - before[1]) == (1, 1)
    be.flush_record()
    records = be.telemetry.recorder.records()
    if telemetry == "off":
        assert records == []
        return
    (rec,) = records
    assert rec["verbs"] == ["filter", "prioritize"]
    assert (rec["dispatches"], rec["snapshots"]) == (1, 1)
    assert (rec["evaluations"], rec["eval_reused"]) == (1, 1)


def _between(case, be):
    """What happens between a pod's `filter` and its `prioritize`."""
    if case == "a-pod-bound-by-someone-else":
        be.observe_pod(mkpod("foreign", cpu="2", node_name="n0"))
    elif case == "a-node-update":
        be.observe_node(mknode("n2", cpu=2, labels={HOST: "n2", ZONE: "z2",
                                                    "pool": "a"}))
    elif case == "a-node-delete":
        be.forget_node("n6")
    elif case == "a-pod-delete":
        be.forget_pod("default/web-0-0")
    elif case == "bind-before-prioritize":
        res = be.bind(ExtenderBindingArgs(
            pod_name="asks", pod_namespace="default", pod_uid="u-asks",
            node="n0"))
        assert res.error == ""


@pytest.mark.parametrize("case", [
    "a-pod-bound-by-someone-else", "a-node-update", "a-node-delete",
    "a-pod-delete", "bind-before-prioritize"])
def test_a_mirror_that_moved_between_the_verbs_is_evaluated_afresh(
        case, monkeypatch):
    be = _small_mirror(binder=lambda pod, node: True)
    pod = _asking_pod("plain")
    be.filter(ExtenderArgs(pod=pod_to_v1(pod), node_names=NAMES))
    _between(case, be)
    taken, before = _counting(be, monkeypatch), _evaluations()
    prio = be.prioritize(ExtenderArgs(pod=pod_to_v1(pod), node_names=NAMES))
    computed, reused = _evaluations()
    assert (len(taken), computed - before[0], reused - before[1]) == (1, 1, 0)
    _p, _f, want = _reference_verbs(be, pod, [], NAMES)
    assert [(h.host, h.score) for h in prio] == want
    # and what it was evaluated afresh for is kept in its turn
    n = len(taken)      # the reference took a snapshot of its own
    be.prioritize(ExtenderArgs(pod=pod_to_v1(pod), node_names=NAMES[:3]))
    assert len(taken) == n and _evaluations()[1] - reused == 1


@pytest.mark.parametrize("case", ["another-uid", "no-filter-before"])
def test_a_prioritize_that_is_not_the_kept_pods_is_evaluated_afresh(
        case, monkeypatch):
    be = _small_mirror()
    pod = _asking_pod("anti")
    if case == "another-uid":
        other = mkpod("other", cpu="100m", uid="u-other")
        be.filter(ExtenderArgs(pod=pod_to_v1(other), node_names=NAMES))
    taken, before = _counting(be, monkeypatch), _evaluations()
    prio = be.prioritize(ExtenderArgs(pod=pod_to_v1(pod), node_names=NAMES))
    computed, reused = _evaluations()
    assert (len(taken), computed - before[0], reused - before[1]) == (1, 1, 0)
    _p, _f, want = _reference_verbs(be, pod, [], NAMES)
    assert [(h.host, h.score) for h in prio] == want
    assert {s for (h, s) in want if h in ("n1", "n2", "n5")} == {0}


@pytest.mark.parametrize("echo_onto, reused_want", [("the-assumed-node", 1),
                                                    ("another-node", 0)])
def test_the_echo_of_the_backends_own_binding(echo_onto, reused_want,
                                              monkeypatch):
    """`bind` assumes the first pod on n3; its informer echo lands between
    the second pod's two verbs. Confirmed onto n3 it changes no row and the
    kept evaluation stands; onto another node the mirror has moved."""
    be = _small_mirror(binder=lambda pod, node: True)
    first = mkpod("first", cpu="1", uid="u-first", labels={"app": "db"})
    be.filter(ExtenderArgs(pod=pod_to_v1(first), node_names=NAMES))
    assert be.bind(ExtenderBindingArgs(
        pod_name="first", pod_namespace="default", pod_uid="u-first",
        node="n3")).error == ""
    assert be.cache.is_assumed("default/first")

    pod = _asking_pod("anti")
    flt = be.filter(ExtenderArgs(pod=pod_to_v1(pod), node_names=NAMES))
    assert "n3" in flt.failed_nodes      # the assumed pod is counted there
    generation = be.cache.generation
    first.node_name = "n3" if echo_onto == "the-assumed-node" else "n4"
    be.observe_pod(first)
    assert not be.cache.is_assumed("default/first")
    assert be.cache.generation > generation     # the cache's own count moves
    taken, before = _counting(be, monkeypatch), _evaluations()
    prio = be.prioritize(ExtenderArgs(pod=pod_to_v1(pod), node_names=NAMES))
    computed, reused = _evaluations()
    assert (len(taken), reused - before[1]) == (1 - reused_want, reused_want)
    assert computed - before[0] == 1 - reused_want
    # either way the scores are those of a snapshot taken now
    _p, _f, want = _reference_verbs(be, pod, [], NAMES)
    assert [(h.host, h.score) for h in prio] == want
    moved_to = dict(want)[first.node_name]
    assert moved_to == 0


def test_a_filter_always_evaluates_and_an_expired_assume_moves_the_epoch():
    be = _small_mirror(binder=lambda pod, node: True)
    pod = pod_to_v1(_asking_pod("plain"))
    before = _evaluations()
    for _ in range(2):      # a retry of the stock scheduler: the same UID
        be.filter(ExtenderArgs(pod=pod, node_names=NAMES))
    assert _evaluations()[0] - before[0] == 2
    be.bind(ExtenderBindingArgs(pod_name="asks", pod_namespace="default",
                                pod_uid="u-asks", node="n0"))
    # its echo never comes: the next filter's cleanup expires it
    epoch = be._epoch
    be.cache._ttl = 0.0
    be.cache.finish_binding("default/asks", be.telemetry.clock() - 1.0)
    flt = be.filter(ExtenderArgs(pod=pod_to_v1(mkpod("next", uid="u-next")),
                                 node_names=NAMES))
    assert be.cache.get_pod("default/asks") is None and be._epoch > epoch
    assert "n0" in flt.node_names


# --------------------------------------------------------------------------- #
# real HTTP (httptest rung)
# --------------------------------------------------------------------------- #


def test_http_extender_end_to_end():
    be = _backend_with_cluster()
    with ExtenderServer(be) as srv:
        cfg = ExtenderConfig(
            url_prefix=srv.url, filter_verb="filter", prioritize_verb="prioritize",
            preempt_verb="preemption", bind_verb="bind", weight=2,
            node_cache_capable=True,
        )
        ext = HTTPExtender(cfg)
        nodes = [mknode("big", cpu=8), mknode("small", cpu=1)]

        passing, failed = ext.filter(mkpod("p", cpu="2"), nodes)
        assert passing == ["big"] and "small" in failed

        scores, weight = ext.prioritize(mkpod("p", cpu="2"), nodes)
        assert weight == 2 and set(scores) == {"big", "small"}

        ext.bind(mkpod("p", cpu="2"), "big")
        assert be.bound == [("default/p", "big")]
    assert srv.requests_served == 3


def test_http_server_speaks_reference_wire_format():
    """Byte-level check: a raw POST shaped like the Go HTTPExtender's
    (capitalized JSON keys) gets a correctly shaped reply."""
    be = ExtenderBackend()
    be.sync_nodes([mknode("n0", cpu=4)])
    with ExtenderServer(be) as srv:
        payload = json.dumps({
            "Pod": pod_to_v1(mkpod("p", cpu="1")),
            "NodeNames": ["n0"],
            "Nodes": None,
        }).encode()
        req = urllib.request.Request(
            srv.url + "/filter", data=payload,
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=5) as resp:
            out = json.loads(resp.read())
        assert out["NodeNames"] == ["n0"]
        assert out["FailedNodes"] == {} and out["Error"] == ""

        # healthz (server.go:216-227 analog)
        with urllib.request.urlopen(srv.url.rsplit("/", 1)[0] + "/healthz") as resp:
            assert resp.read() == b"ok"


def test_http_extender_ignorable_and_managed_resources():
    cfg = ExtenderConfig(url_prefix="http://127.0.0.1:1/dead", filter_verb="filter",
                         managed_resources=("example.com/tpu",), ignorable=True)
    ext = HTTPExtender(cfg)
    assert not ext.is_interested(mkpod("plain"))
    rich = mkpod("rich")
    rich.requests = Resources(milli_cpu=100, scalars=(("example.com/tpu", 4),))
    assert ext.is_interested(rich)


# --------------------------------------------------------------------------- #
# our scheduler calling OUT to extenders (HTTPExtender client in the cycle)
# --------------------------------------------------------------------------- #


def test_scheduler_with_extender_in_cycle():
    """A second ExtenderBackend acts as the external webhook; our Scheduler
    consults it per pod: its filter veto and its bind verb both take effect."""
    from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

    # external extender that only admits node "allowed"
    class VetoBackend(ExtenderBackend):
        def filter(self, args):
            res = super().filter(args)
            keep = [n for n in (res.node_names or []) if n == "allowed"]
            res.node_names = keep
            return res

    ext_be = VetoBackend()
    ext_be.sync_nodes([mknode("allowed", cpu=8), mknode("forbidden", cpu=8)])

    with ExtenderServer(ext_be) as srv:
        cfg = ExtenderConfig(url_prefix=srv.url, filter_verb="filter",
                             prioritize_verb="prioritize", bind_verb="bind",
                             node_cache_capable=True)
        binder = RecordingBinder()
        s = Scheduler(binder=binder, extenders=[HTTPExtender(cfg)])
        s.on_node_add(mknode("allowed", cpu=8))
        s.on_node_add(mknode("forbidden", cpu=8))
        for i in range(3):
            s.on_pod_add(mkpod(f"p{i}", cpu="1"))
        stats = s.schedule_pending()
        assert stats.scheduled == 3
        # every pod landed on the only extender-approved node, bound via the
        # extender's bind verb (not the local binder)
        assert all(n == "allowed" for _, n in ext_be.bound)
        assert len(ext_be.bound) == 3 and binder.bound == []


# --------------------------------------------------------------------------- #
# the served extender: informers feed the mirror, bind assumes and writes
# through the apiserver, the verbs compile ahead, one record a pod (ISSUE 34)
# --------------------------------------------------------------------------- #

import contextlib  # noqa: E402
import time  # noqa: E402


from benchmarks.harness import objects  # noqa: E402
from kubernetes_tpu.extender import ServedExtender  # noqa: E402
from kubernetes_tpu.state.dims import Dims  # noqa: E402

#: a seeded 64-node cluster of the flagship's shape: all four roles, two
#: groups each, 16 replicas of every group bound by the flagship's rule
SEEDED = {"nodes": 64, "zones": 4, "racks_per_zone": 2, "node_cpu": "8000m",
          "node_memory": "33554432Ki", "node_pods": 110, "groups": 8,
          "roles": {"plain": 2, "spread": 2, "anti": 2, "affinity": 2},
          "zone_spread": True,
          "request_tiers": [["100m", "131072Ki"], ["500m", "1048576Ki"]]}


@contextlib.contextmanager
def served_cluster(seed=7, bound=128):
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client

    api = APIServer()
    client = Client.local(api)
    groups = objects.Groups(SEEDED, seed, bound // SEEDED["groups"])
    for n in objects.make_nodes(SEEDED):
        client.nodes.create(n)
    for p in objects.prebound_pods(groups, SEEDED["nodes"], bound):
        client.pods.create(p)
    # capacities provisioned ahead, as an operator's are: no pod of a test
    # crosses a bucket (the 129th bound pod would double E and recompile)
    served = ServedExtender(client, base_dims=Dims(N=64, E=512)).start()
    try:
        yield client, served, groups
    finally:
        served.stop()
        api.close()


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not cond():
        time.sleep(0.01)
    return cond()


def _mirror(backend):
    return ({n.name: (tuple(sorted(n.labels.items())), n.allocatable)
             for n in backend.cache.nodes()},
            {p.key: (p.node_name, tuple(sorted(p.labels.items())), p.requests)
             for p in backend.cache.scheduled_pods()})


def _post(url, verb, body, timeout=5.0):
    req = urllib.request.Request(
        f"{url}/{verb}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _role_group(groups, role):
    return next(g for g in range(groups.n) if groups.role[g] == role)


def test_the_informer_fed_mirror_equals_a_synced_one():
    with served_cluster() as (client, served, groups):
        be = served.backend
        assert be.cache.node_count == 64 and be.cache.pod_count == 128
        # adds, updates and deletes, one watch event each
        extra = objects.make_nodes({**SEEDED, "nodes": 66})[64:]
        for n in extra:
            client.nodes.create(n)
        relabelled = client.nodes.get("node-3", "")
        relabelled["metadata"]["labels"]["tier"] = "gold"
        client.nodes.update(relabelled)
        client.nodes.delete("node-65", "")
        client.pods.create(groups.pod(0, "late-bound", "node-64"))
        client.pods.create(groups.pod(1, "pending"))       # not the mirror's
        client.pods.bind("pending", "node-5", "default")   # now it is
        moved = client.pods.get("base-g2-0", "default")
        moved["metadata"]["labels"]["track"] = "canary"
        client.pods.update(moved)
        client.pods.delete("base-g3-1", "default")
        assert _wait(lambda: be.cache.pod_count == 129
                     and be.cache.get_node("node-65") is None
                     and "track" in be.cache.get_pod("default/base-g2-0").labels
                     and "tier" in be.cache.get_node("node-3").labels)
        # one relist: the watch is down while the cluster changes, and the
        # token it would resume from is gone
        inf = served.pod_informer
        inf.stop()
        client.pods.delete("base-g4-2", "default")
        client.pods.create(groups.pod(5, "during-the-gap", "node-9"))
        gone = client.pods.get("base-g6-3", "default")
        gone["metadata"]["labels"]["track"] = "stable"
        client.pods.update(gone)
        relists = inf.relists
        inf.last_sync_rv = ""
        inf.start()
        assert _wait(lambda: inf.relists == relists + 1
                     and be.cache.get_pod("default/during-the-gap") is not None
                     and be.cache.get_pod("default/base-g4-2") is None)

        synced = ExtenderBackend()
        synced.sync_nodes([node_from_v1(n)
                           for n in client.nodes.list()["items"]])
        synced.sync_scheduled_pods([pod_from_v1(p) for p in
                                    client.pods.list("default")["items"]])
        assert _mirror(be) == _mirror(synced)
        assert len(_mirror(be)[0]) == 65 and len(_mirror(be)[1]) == 129


def test_a_filter_after_a_bind_sees_the_pod_before_its_echo():
    with served_cluster() as (client, served, groups):
        be, anti = served.backend, _role_group(groups, "anti")
        names = [f"node-{i}" for i in range(64)]
        # a stock scheduler sends the pod as the apiserver holds it
        first = client.pods.create(groups.pod(anti, "anti-a"))
        second = client.pods.create(groups.pod(anti, "anti-b"))
        assert _wait(lambda: served.pod_informer.lister.get(
            "default", "anti-b") is not None)
        _, flt = _post(served.url, "filter", {"Pod": first, "NodeNames": names})
        host = flt["NodeNames"][0]
        served.pod_informer.stop()     # the echo of the Binding is held back
        code, res = _post(served.url, "bind", {
            "PodName": "anti-a", "PodNamespace": "default",
            "PodUID": first["metadata"]["uid"], "Node": host})
        assert (code, res["Error"]) == (200, "")
        # written through the apiserver, assumed in the mirror, unconfirmed
        assert client.pods.get("anti-a", "default")["spec"]["nodeName"] == host
        assert be.cache.is_assumed("default/anti-a")
        _, flt = _post(served.url, "filter", {"Pod": second, "NodeNames": names})
        assert host not in flt["NodeNames"]
        assert "anti-affinity" in flt["FailedNodes"][host]
        # the echo confirms the assumed pod: one pod, no longer assumed
        served.pod_informer.start()
        assert _wait(lambda: not be.cache.is_assumed("default/anti-a"))
        assert be.cache.get_pod("default/anti-a").node_name == host
        # a Binding the apiserver refuses is forgotten, and says so
        code, res = _post(served.url, "bind", {
            "PodName": "anti-b", "PodNamespace": "default",
            "PodUID": "not-its-uid", "Node": host})
        assert code == 200 and res["Error"]
        assert be.cache.get_pod("default/anti-b") is None


@pytest.mark.parametrize("role", ["plain", "spread", "anti", "affinity"])
def test_served_answers_equal_the_oracle(role):
    import chip_smoke

    with served_cluster(seed=11) as (client, served, groups):
        nodes = [node_from_v1(n) for n in client.nodes.list()["items"]]
        by_name = {n.name: n for n in nodes}
        world = [pod_from_v1(p) for p in client.pods.list("default")["items"]]
        names = [n.name for n in nodes] + ["ghost"]
        v1 = groups.pod(_role_group(groups, role), f"asks-{role}")
        pod = pod_from_v1(v1)
        code, flt = _post(served.url, "filter", {"Pod": v1, "NodeNames": names})
        assert (code, flt["Error"]) == (200, "")
        passing = set(flt["NodeNames"])
        want = {n.name for n in nodes
                if chip_smoke.oracle_fits(pod, n, nodes, world, by_name)}
        assert passing == want and 0 < len(want)
        assert set(flt["FailedNodes"]) == set(names) - passing
        assert all(flt["FailedNodes"].values())
        code, prio = _post(served.url, "prioritize",
                           {"Pod": v1, "NodeNames": flt["NodeNames"]})
        assert code == 200
        assert [h["Host"] for h in prio] == flt["NodeNames"]
        assert all(isinstance(h["Score"], int) and 0 <= h["Score"] <= 10
                   for h in prio)


def test_one_record_a_pod_with_its_phases_counters_and_children():
    from kubernetes_tpu.extender.server import (EXTENDER_REQUEST_DURATION,
                                                EXTENDER_REQUESTS)

    with served_cluster() as (client, served, groups):
        tel = served.backend.telemetry
        names = [f"node-{i}" for i in range(64)]
        served0 = EXTENDER_REQUESTS.value(verb="bind", code="200")
        pods = [client.pods.create(groups.pod(_role_group(groups, r),
                                              f"rec-{r}"))
                for r in ("anti", "spread")]
        for v1 in pods:
            _, flt = _post(served.url, "filter",
                           {"Pod": v1, "NodeNames": names})
            _, prio = _post(served.url, "prioritize",
                            {"Pod": v1, "NodeNames": flt["NodeNames"]})
            _post(served.url, "bind", {
                "PodName": v1["metadata"]["name"], "PodNamespace": "default",
                "PodUID": v1["metadata"]["uid"], "Node": prio[0]["Host"]})
        assert _wait(lambda: len(tel.recorder.records()) == 2)
        anti, spread = tel.recorder.records()
        assert anti["pod"] == "default/rec-anti" and anti["seq"] == 1
        assert anti["verbs"] == ["filter", "prioritize", "bind"]
        assert anti["stats"]["attempted"] == 1
        assert anti["stats"]["scheduled"] == 1
        phases = [name for name, _dt in anti["phases"]]
        assert phases == [
            "decode", "snapshot", "dispatch", "readback", "answer",  # filter
            "caller", "decode", "answer",           # prioritize: kept arrays
            "caller", "decode", "bind-commit", "answer"]
        assert anti["duration_s"] == pytest.approx(
            sum(dt for _name, dt in anti["phases"]), abs=2e-3)
        # one evaluation a pod, refused somewhere or not: `filter`'s one
        # snapshot and one program, and `prioritize` cut from its arrays
        # (the previous pod's echo between the two verbs does not move the
        # mirror's epoch)
        for rec in (anti, spread):
            assert (rec["dispatches"], rec["snapshots"]) == (1, 1)
            assert (rec["evaluations"], rec["eval_reused"]) == (1, 1)
        assert spread["feasible"] == 64 > anti["feasible"]
        assert set(anti["device_split"]) == {"launch_s", "execute_s",
                                             "readback_s"}
        for path in ("bind-commit/assume", "bind-commit/bind-call",
                     "bind-commit/bind-call/apiserver.bind",
                     "bind-commit/bind-call/apiserver.bind/store.txn",
                     "bind-commit/finish", "snapshot/prepare"):
            assert anti["children"][path][0] >= 1, path
        # the second pod's filter came after the first's Binding: either
        # the echo was in (0) or it was still out (1); never lost
        assert spread["assumed_outstanding"] in (0, 1)
        assert spread["informer_relists"] == 0 and "pump_lag_max" in spread
        # counted once the reply's last byte is out: the client may be ahead
        assert _wait(lambda: EXTENDER_REQUESTS.value(
            verb="bind", code="200") == served0 + 2)
        assert EXTENDER_REQUEST_DURATION.count(verb="filter") >= 2


def test_telemetry_off_records_nothing_and_answers_the_same(monkeypatch):
    monkeypatch.setenv("KTPU_TELEMETRY", "0")
    with served_cluster() as (client, served, groups):
        v1 = client.pods.create(groups.pod(_role_group(groups, "anti"),
                                           "quiet"))
        # with no record to carry the filter's pod, `bind` looks the pod up
        # in the informer's store: let its watch event land first
        assert _wait(lambda: served.pod_informer.lister.get(
            "default", "quiet") is not None)
        names = [f"node-{i}" for i in range(64)]
        _, flt = _post(served.url, "filter", {"Pod": v1, "NodeNames": names})
        assert 0 < len(flt["NodeNames"]) < 64
        _, res = _post(served.url, "bind", {
            "PodName": "quiet", "PodNamespace": "default",
            "PodUID": v1["metadata"]["uid"], "Node": flt["NodeNames"][0]})
        assert res["Error"] == ""
        assert served.backend.telemetry.recorder.records() == []
        # nor does the start pay for an account nobody keeps (ISSUE 37):
        # no Trace under a handler or the compile-ahead, the round's three
        # stages alone (a handful of clock reads)
        assert served.start_log == []
        for informer in (served.node_informer, served.pod_informer):
            assert not informer.trace_below and list(
                informer.last_sync["children"]) == ["list", "index",
                                                    "handlers"]


def test_no_verb_compiles_after_start_returns():
    import jax.monitoring

    events, armed = [], [False]

    def listen(event, duration, **kw):   # jax keeps a listener for good
        if armed[0] and event == "/jax/core/compile/backend_compile_duration":
            events.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(listen)

    with served_cluster() as (client, served, groups):
        assert [name for _d, name in served.warm_log] == ["evaluate"]
        assert [name for name, _s in served.start_log] == [
            "nodes-sync", "pods-sync", "compile-ahead", "socket"]
        names = [f"node-{i}" for i in range(64)]
        armed[0] = True
        try:
            # pods of every role, each bound before the next is filtered:
            # the snapshot after a Binding is a patch of the resident planes
            for role in ("anti", "affinity", "spread", "plain"):
                v1 = client.pods.create(
                    groups.pod(_role_group(groups, role), f"cold-{role}"))
                _, flt = _post(served.url, "filter",
                               {"Pod": v1, "NodeNames": names})
                _, prio = _post(served.url, "prioritize",
                                {"Pod": v1, "NodeNames": flt["NodeNames"]})
                _, res = _post(served.url, "bind", {
                    "PodName": v1["metadata"]["name"],
                    "PodNamespace": "default",
                    "PodUID": v1["metadata"]["uid"],
                    "Node": prio[0]["Host"]})
                assert res["Error"] == ""
        finally:
            armed[0] = False
        assert events == []
        # ISSUE 37: `start_log` and the first pod's `loop` are ONE account
        first, *later = served.backend.telemetry.recorder.records()
        loop = first["loop"]
        stages = {p: v for p, v in loop["children"].items()
                  if p.count("/") == 1}
        assert [(p.split("/")[1], v[1]) for p, v in stages.items()] \
            == served.start_log
        assert [n for n, _s in loop["phases"]] == ["start"]
        assert sum(v[1] for v in stages.values()) == pytest.approx(
            loop["phases"][0][1], rel=0.02, abs=1e-3)
        assert loop["synced"] == {"start/nodes-sync": True,
                                  "start/pods-sync": True}
        below = set(loop["children"])
        assert below >= {
            "start/pods-sync/list/apiserver.list/store.list/kv",
            "start/pods-sync/index", "start/pods-sync/handlers/decode",
            "start/nodes-sync/handlers/decode",
            "start/compile-ahead/snapshot", "start/compile-ahead/evaluate",
            "start/compile-ahead/patch-ladder"}
        assert loop["children"]["start/pods-sync/handlers/decode"][0] == 128
        assert len(later) == 3 and not any("loop" in r for r in later)
        assert all("gc_pause_s" in r for r in [first] + later)
