"""ISSUE 41: one preemption pass serves every replica of a template.

The what-if (ops/preempt.py) returns, per lane, the candidate nodes in
pickOneNodeForPreemption's order and the reprieve scan's victims on every one
of them; the host (sched/preemption.py) hands the lane's k-th pending replica
the k-th node not yet handed out, publishes the nomination, then evicts. Held
here to the benchmark's plain sequential reference
(benchmarks/harness/checks/preemption.py `sequential_pass`: upstream's pass,
one preemptor at a time, earlier ones nominated), and driven through the API
and through the cell's rehearsal (`preempt-5k.backlog`) with its controls.
"""

import json
import os
import random
import subprocess
import sys
import threading
import time

import pytest

from benchmarks.harness import cell
from benchmarks.harness.checks import preemption as ref
from benchmarks.harness.kinds import preempt_backlog
from benchmarks.harness.shapes import priority_fill
from benchmarks.harness.sources import field_count, preempt_roofline
from kubernetes_tpu.api.types import (Affinity, LabelSelector, Node, Pod,
                                      PodAffinityTerm, Resources)
from kubernetes_tpu.sched.preemption import Preemptor
from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler
from kubernetes_tpu.state.dims import Dims

ROOT = cell.ROOT
HOSTNAME = "kubernetes.io/hostname"
CFG = cell.load_json(ROOT, "benchmarks", "configs", "preempt-5k.json")
SMALL = {**CFG, **CFG["rehearse"]}
#: one Dims bucket for every cluster below: ONE compiled what-if
DIMS = Dims(N=16, P=32, E=128)
MI = 1024


# --------------------------------------------------------------------- #
# a cluster both as the program's objects and as the reference's dicts
# --------------------------------------------------------------------- #


def node_pair(name: str, cpu: int) -> tuple:
    return (Node(name=name, labels={HOSTNAME: name},
                 allocatable=Resources.make(cpu=f"{cpu}m", memory="64Gi",
                                            pods=110)),
            {"name": name, "cpu": cpu, "memory": 64 * MI * MI, "pods": 110})


def pod_pair(name: str, cpu: int, priority: int, idx: int, node: str = "",
             **aff) -> tuple:
    p = Pod(name=name, priority=priority, creation_index=idx,
            requests=Resources.make(cpu=f"{cpu}m", memory="64Mi"),
            labels={"app": name.split("-")[0]})
    if aff:
        p.affinity = Affinity(**aff)
    p.node_name = node
    return p, {"name": f"default/{name}", "cpu": cpu, "memory": 64 * MI,
               "priority": priority, "start": idx, "node": node}


def random_cluster(seed: int) -> tuple:
    """Mixed node sizes, three priorities among the bound pods, uneven fill
    (no node keeps room for a preemptor), two preemptor templates that want
    the same nodes, more preemptors than candidate nodes. Every preemptor
    asks more than half the largest node, so no node takes two and the
    one-pass hand-out is upstream's own sequence."""
    rng = random.Random(seed)
    nodes = [node_pair(f"n{i}", rng.choice([2000, 3000, 4000, 4000]))
             for i in range(rng.randint(6, 10))]
    bound, idx = [], 0
    for _node, spec in nodes:
        used, room = 0, rng.choice([0, 100, 400, 900, 1500])
        while True:
            cpu = rng.choice([300, 500, 700, 1100])
            if used + cpu > spec["cpu"] - room:
                break
            bound.append(pod_pair(f"e-{idx}", cpu, rng.randrange(3), idx,
                                  spec["name"]))
            used, idx = used + cpu, idx + 1
    n_pre = len(nodes) + 2
    pending = [pod_pair(f"vip-{j}", *rng.choice([(2100, 10), (2600, 10),
                                                 (2600, 20)]), 1000 + j)
               for j in range(n_pre)]
    return nodes, bound, pending


def one_pass(nodes, bound, pending) -> tuple:
    """The program's side: ONE wave (nothing fits, so the pass runs over
    every pending pod). -> (scheduler, {preemptor key: node}, {node: victim
    keys})."""
    s = Scheduler(binder=RecordingBinder(), clock=lambda: 0.0,
                  preemptor=Preemptor(), base_dims=DIMS)
    for n, _ in nodes:
        s.on_node_add(n)
    where = {}
    for p, spec in bound:
        s.on_pod_add(p)
        where[spec["name"]] = spec["node"]
    for p, _ in pending:
        s.on_pod_add(p)
    st = s.schedule_pending()
    assert st.scheduled == 0, "the cluster was to have no room"
    sent = {p.key: s.queue.nominated_node(p.key) for p, _ in pending}
    victims: dict = {}
    for key in s.preemptor.evictor.evicted:
        victims.setdefault(where[key], set()).add(key)
    return s, sent, victims


@pytest.mark.parametrize("seed", range(4100, 4112))
def test_one_pass_hands_out_what_upstreams_sequence_would(seed):
    nodes, bound, pending = random_cluster(seed)
    s, sent, victims = one_pass(nodes, bound, pending)
    want = ref.sequential_pass([n for _, n in nodes], [b for _, b in bound],
                               [p for _, p in pending])
    want_sent = {name: node for name, node, _v in want}
    want_victims = {node: {v["name"] for v in vs}
                    for _name, node, vs in want if node is not None}
    assert any(node is None for node in want_sent.values()), \
        "more preemptors than candidate nodes, by construction"
    assert sum(1 for n in want_sent.values() if n) >= 2
    # the same victim COUNT a node, and the same multiset of keys
    assert {n: len(v) for n, v in victims.items()} == \
        {n: len(v) for n, v in want_victims.items()}
    by_key = {b["name"]: b for _, b in bound}
    assert sorted(ref.node_key([by_key[k] for k in v])
                  for v in victims.values()) == \
        sorted(ref.node_key(vs) for _n, node, vs in want if node)
    # the reference breaks a tie of the keys as the program does (the node
    # listed first = the lower index in the snapshot's node order), so the
    # identities agree too, tied or not
    assert sent == want_sent
    assert victims == want_victims
    assert s.preemptor.last_pass["preempt_dispatches"] == 1
    assert s.preemptor.last_pass["preempt_nodes_handed_out"] == \
        len(want_victims)


def test_a_lane_with_more_replicas_than_candidate_nodes():
    """Three nodes can take a preemptor, five replicas wait: three are
    nominated on distinct nodes in ONE pass, two stay unschedulable, and no
    node is evicted for a replica that was sent nowhere."""
    nodes = [node_pair(f"n{i}", 4000) for i in range(3)] \
        + [node_pair("small", 2000)]
    bound = [pod_pair(f"e-{4 * i + j}", 900, 0, 4 * i + j, f"n{i}")
             for i in range(3) for j in range(4)] \
        + [pod_pair("e-small", 1800, 0, 50, "small")]
    pending = [pod_pair(f"vip-{j}", 3000, 10, 100 + j) for j in range(5)]
    s, sent, victims = one_pass(nodes, bound, pending)
    assert sorted(n for n in sent.values() if n) == ["n0", "n1", "n2"]
    assert [sent[f"default/vip-{j}"] for j in (3, 4)] == [None, None]
    assert {n: len(v) for n, v in victims.items()} == \
        {"n0": 3, "n1": 3, "n2": 3}
    last = s.preemptor.last_pass
    assert (last["preempt_lanes"], last["preempt_preemptors"],
            last["preempt_nominated"], last["preempt_victims"],
            last["preempt_nodes_handed_out"]) == (1, 5, 3, 9, 3)
    # the next wave binds the three; the other two fail again and find no
    # candidate left (each node holds a preemptor of their own priority)
    st = s.schedule_pending()
    assert sorted(st.assignments.values()) == ["n0", "n1", "n2"]
    assert len(s.preemptor.evictor.evicted) == 9


def test_two_lanes_contending_for_the_same_nodes_share_none():
    """Two templates, both best served by the same node: it goes to the one
    that is first in the queue, the other takes its own next choice in the
    same pass; no victim is named twice."""
    nodes = [node_pair("cheap", 4000), node_pair("dear", 4000)]
    bound = [pod_pair(f"c-{j}", 900, 0, j, "cheap") for j in range(4)] \
        + [pod_pair(f"d-{j}", 900, 1, 10 + j, "dear") for j in range(4)]
    pending = [pod_pair("big-0", 3000, 10, 100),
               pod_pair("wide-0", 2500, 10, 101)]
    s, sent, victims = one_pass(nodes, bound, pending)
    assert sent == {"default/big-0": "cheap", "default/wide-0": "dear"}
    assert {n: len(v) for n, v in victims.items()} == {"cheap": 3, "dear": 3}
    assert s.preemptor.last_pass["preempt_lanes"] == 2
    assert s.preemptor.last_pass["preempt_retry_soon"] == 0
    want = ref.sequential_pass([n for _, n in nodes], [b for _, b in bound],
                               [p for _, p in pending])
    assert [(name, node, len(vs)) for name, node, vs in want] == [
        ("default/big-0", "cheap", 3), ("default/wide-0", "dear", 3)]


def test_a_lane_whose_replicas_repel_each_other_keeps_one_node_a_pass():
    """Host anti-affinity among a template's own replicas: the what-if did
    not see the first replica where it was sent, so the lane takes ONE node a
    pass and its other replicas retry promptly (docs/PARITY.md)."""
    term = PodAffinityTerm(selector=LabelSelector.of(
        match_labels={"app": "vip"}), topology_key=HOSTNAME)
    nodes = [node_pair(f"n{i}", 4000) for i in range(3)]
    bound = [pod_pair(f"e-{4 * i + j}", 900, 0, 4 * i + j, f"n{i}")
             for i in range(3) for j in range(4)]
    pending = [pod_pair(f"vip-{j}", 3000, 10, 100 + j,
                        anti_required=(term,)) for j in range(3)]
    s, sent, victims = one_pass(nodes, bound, pending)
    # of three equal nodes the one whose victims started latest (key 5)
    assert sorted(n for n in sent.values() if n) == ["n2"]
    assert {n: len(v) for n, v in victims.items()} == {"n2": 3}
    last = s.preemptor.last_pass
    assert (last["preempt_nominated"], last["preempt_retry_soon"],
            last["preempt_nodes_handed_out"]) == (1, 2, 1)
    # a pass a wave: three waves later all three are bound, one a node
    bound_on = {}
    for _ in range(4):
        bound_on.update(s.schedule_pending().assignments)
    assert sorted(bound_on.values()) == ["n0", "n1", "n2"]
    assert len(s.preemptor.evictor.evicted) == 9


def test_the_what_if_returns_an_order_and_one_mask_a_lane():
    """The read-back stays small: per lane an [N] order whose first
    n_candidates entries are the candidates, best first, and ONE [E] mask
    that holds every candidate node's victims; `node` / `victims` (PR 30's
    pinned results) are its head."""
    import jax
    import numpy as np

    from kubernetes_tpu.sched.cycle import snapshot_with_keys
    from kubernetes_tpu.sched.preemption import _preempt
    from kubernetes_tpu.ops.lattice import default_engine_config
    import jax.numpy as jnp

    nodes, bound, pending = random_cluster(4100)
    s = Scheduler(binder=RecordingBinder(), clock=lambda: 0.0,
                  base_dims=DIMS)
    for n, _ in nodes:
        s.on_node_add(n)
    for p, _ in bound:
        s.on_pod_add(p)
    snap, (uk, ev) = snapshot_with_keys(
        s.cache, s.encoder, [p for p, _ in pending[:2]], DIMS)
    E = snap.existing.valid.shape[0]
    res = jax.device_get(_preempt(
        snap.tables, snap.existing, snap.pending.cls[:2],
        snap.pending.node_name_req[:2], snap.pending.priority[:2],
        snap.dims.D, (uk, ev), jnp.zeros((E,), bool), jnp.float32(1.0),
        default_engine_config()))
    N = snap.tables.nodes.valid.shape[0]
    assert res.order.shape == (2, N) and res.node_victims.shape == (2, E)
    node_of = np.asarray(jax.device_get(snap.existing.node_id))
    for lane in range(2):
        k = int(res.n_candidates[lane])
        assert k >= 2 and bool(res.bulk[lane])
        assert int(res.order[lane][0]) == int(res.node[lane])
        assert sorted(res.order[lane]) == list(range(N))
        on_best = res.node_victims[lane] & (node_of == res.node[lane])
        assert np.array_equal(on_best, res.victims[lane])
        # victims only on candidate nodes, and some on every one of them
        cand = set(int(n) for n in res.order[lane][:k])
        assert set(node_of[res.node_victims[lane]].tolist()) == cand


# --------------------------------------------------------------------- #
# through the API: the nomination is published, before the deletes
# --------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def served():
    """The rehearsal's cluster through the benchmark's own wiring, the
    backlog bound, the client's raw watch events kept in order."""
    from benchmarks.harness.probes import wait_until
    from benchmarks.harness.traffic import create_all
    from benchmarks.harness.wirings import local

    cluster = local.Cluster(SMALL)
    client = cluster.client
    shapes = priority_fill.Population(SMALL, 41, SMALL["backlog_pods"])
    nodes = priority_fill.make_nodes(SMALL)
    prebound = shapes.prebound(SMALL["nodes"], SMALL["existing_pods"])
    create_all(client.nodes, nodes)
    create_all(client.pods, prebound)
    pods = shapes.pending(SMALL["backlog_pods"], 41, "job")
    listing = client.pods.list("default")
    watch = client.pods.watch(
        "default", resource_version=listing["metadata"]["resourceVersion"])
    events, stop = [], threading.Event()

    def pump():
        while not stop.is_set():
            ev = watch.next(timeout=0.2)
            if ev is not None:
                events.append((ev.type, ev.object))

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    create_all(client.pods, pods)
    server = cluster.new_server()
    server.start()
    names = {p["metadata"]["name"] for p in pods}

    def bound():
        return sum(1 for p in client.pods.list("default")["items"]
                   if p["metadata"]["name"] in names
                   and p["spec"].get("nodeName"))

    try:
        assert wait_until(lambda: bound() == len(names), timeout=300,
                          interval=0.2), f"{bound()} of {len(names)} bound"
        time.sleep(0.5)   # the last echoes reach the watch
        yield {"cluster": cluster, "server": server, "events": list(events),
               "names": names, "nodes": nodes, "prebound": prebound,
               "pods": pods, "shapes": shapes}
    finally:
        stop.set()
        watch.stop()
        t.join(timeout=5)
        cluster.close()


def test_nomination_is_on_the_watch_before_the_first_victims_delete(served):
    """Upstream's order: SetNominatedNodeName, then the deletes. Every
    preemptor's `status.nominatedNodeName` reaches a client's watch before
    ANY pod of the node it names is deleted. Its Binding comes after the
    deletes on the node it lands on, which need not be the one it was
    nominated to: the cycle reserves no nominated room, and upstream too
    lets a nominated pod bind elsewhere."""
    nominated_at, deleted_at, bound_at = {}, {}, {}
    for i, (kind, obj) in enumerate(served["events"]):
        name = obj["metadata"]["name"]
        if kind == "DELETED":
            deleted_at.setdefault(obj["spec"]["nodeName"], []).append(i)
        elif name in served["names"]:
            node = (obj.get("status") or {}).get("nominatedNodeName")
            if node and name not in nominated_at:
                nominated_at[name] = (i, node)
            if obj["spec"].get("nodeName"):
                bound_at.setdefault(name, (i, obj["spec"]["nodeName"]))
    assert set(nominated_at) == set(bound_at) == served["names"]
    assert len({node for _i, node in nominated_at.values()}) == 64
    for name, (i, node) in nominated_at.items():
        assert len(deleted_at[node]) == 3
        assert i < min(deleted_at[node]), (name, node)
        at, landed = bound_at[name]
        assert max(deleted_at[landed]) < at
    assert len({node for _i, node in bound_at.values()}) == 64
    assert min(i for i, _ in nominated_at.values()) < \
        min(i for at in deleted_at.values() for i in at)


def test_the_served_run_passes_the_checks_and_the_record_says_one_pass(
        served):
    from benchmarks.harness.checks import placement

    client = served["cluster"].client
    listing = client.pods.list("default")["items"]
    ctx = {"cfg": SMALL, "check_spread": False}
    assert ref.final_state(served["nodes"], listing, ctx) == []
    assert placement.final_state(served["nodes"], listing, ctx) == []
    bound_preemptors = [p for p in listing
                        if p["metadata"]["name"] in served["names"]]
    assert len(bound_preemptors) == 64
    assert all(p["status"]["nominatedNodeName"] for p in bound_preemptors)
    assert len(listing) == 64 + 256 - 3 * 64
    records = [r for r in
               served["server"].scheduler.telemetry.recorder.records()
               if r.get("preempt_preemptors")]
    assert len(records) == 1
    rec = records[0]
    assert (rec["preempt_lanes"], rec["preempt_preemptors"],
            rec["preempt_nominated"], rec["preempt_victims"],
            rec["preempt_nodes_handed_out"], rec["preempt_dispatches"],
            rec["preempt_retry_soon"]) == (1, 64, 64, 192, 64, 1, 0)
    ch = rec["children"]
    for path in ("requeue/snapshot", "requeue/preempt/what-if",
                 "requeue/preempt/nominate", "requeue/preempt/evict"):
        assert ch[path][0] == 1 and ch[path][1] > 0, path
    # the apiserver's and the store's own spans nest below the two new ones
    assert any(p.startswith("requeue/preempt/evict/apiserver.")
               for p in ch)
    assert any(p.startswith("requeue/preempt/nominate/apiserver.")
               for p in ch)
    assert rec["preempt_evict_s"] == pytest.approx(
        ch["requeue/preempt/evict"][1], abs=1e-3)


def test_a_scheduler_that_takes_over_learns_a_listed_pods_nomination():
    """A pending pod that carries `status.nominatedNodeName` at start()
    (published by the scheduler that failed) is in the new queue's
    nominated-pods map; an echo of the write for a pod that is already
    assumed does not re-admit it."""
    from kubernetes_tpu.api.v1 import pod_from_v1

    obj = priority_fill.Population(SMALL, 1, 64).pending(1, 1, "job")[0]
    obj["status"] = {"nominatedNodeName": "node-7"}
    pod = pod_from_v1(obj)
    assert pod.nominated_node_name == "node-7"
    s = Scheduler(binder=RecordingBinder(), clock=lambda: 0.0)
    s.on_pod_add(pod)
    assert s.queue.nominated_node(pod.key) == "node-7"
    # what this process decides itself wins over what it reads back
    s.queue.delete_nominated(pod.key)
    s.queue.add_nominated(pod.key, "node-9")
    s.on_pod_update(pod, pod)
    assert s.queue.nominated_node(pod.key) == "node-9"
    # assumed (bound, echo not seen yet): the status echo is skipped
    s.on_node_add(Node(name="node-9", allocatable=Resources.make(
        cpu="4", memory="32Gi", pods=110)))
    st = s.schedule_pending()
    assert st.assignments == {pod.key: "node-9"}
    before = s.queue.depths()
    s.on_pod_update(pod, pod)
    assert s.queue.depths() == before and not before["active"]


# --------------------------------------------------------------------- #
# the plain reference and the check, without a run
# --------------------------------------------------------------------- #


def _spec(name, cpu, priority, start=0):
    return {"name": name, "cpu": cpu, "memory": 1, "priority": priority,
            "start": start}


NODE4 = {"name": "n", "cpu": 4000, "memory": 1 << 30, "pods": 110}


@pytest.mark.parametrize("on_node, ask, want", [
    # the configuration's arithmetic: 4 x 900m, 3000m asked -> exactly 3
    ([_spec(f"l{j}", 900, 0, j) for j in range(4)], (3000, 10),
     ["l1", "l2", "l3"]),
    # the more important pod is offered its place back first
    ([_spec("mid", 900, 5), _spec("low", 900, 0), _spec("low2", 900, 0, 1),
      _spec("low3", 900, 0, 2)], (3000, 10), ["low", "low2", "low3"]),
    # equal or higher priority is never a victim: no candidate
    ([_spec("peer", 2000, 10)], (3000, 10), None),
    # fits beside everything: a candidate with no victim
    ([_spec("tiny", 500, 0)], (3000, 10), []),
])
def test_select_victims_is_upstreams_reprieve(on_node, ask, want):
    got = ref.select_victims(NODE4, on_node, _spec("vip", *ask))
    assert (None if got is None else [p["name"] for p in got]) == want


def test_pick_one_node_orders_by_upstreams_keys():
    a = [_spec("a", 1, 5, 1)]
    b = [_spec("b1", 1, 3, 1), _spec("b2", 1, 3, 2)]       # lower top
    c = [_spec("c", 1, 3, 9)]                              # lower sum
    d = [_spec("d", 1, 3, 20)]                             # later start
    assert ref.pick_one_node({"a": a, "b": b}, ["a", "b"]) == "b"
    assert ref.pick_one_node({"b": b, "c": c}, ["b", "c"]) == "c"
    assert ref.pick_one_node({"c": c, "d": d}, ["c", "d"]) == "d"
    assert ref.pick_one_node({"x": c, "y": list(c)}, ["y", "x"]) == "y"
    assert ref.pick_one_node({"e": [], "c": c}, ["c", "e"]) == "e"
    assert ref.pick_one_node({}, []) is None


def _final(per_node_fillers: dict, preemptors: dict) -> tuple:
    """(nodes, listing) of a 4-node cluster at the configuration's shapes:
    `per_node_fillers[n]` fillers on node n, `preemptors[n]` = nominated?"""
    cfg = {**SMALL, "nodes": 4, "existing_pods": 16}
    shapes = priority_fill.Population(cfg, 1, 4)
    nodes = priority_fill.make_nodes(cfg)
    listing = [p for p in shapes.prebound(4, 16)
               if int(p["metadata"]["name"][5:]) % 4
               < per_node_fillers[int(p["spec"]["nodeName"][5:])]]
    for n, nominated in preemptors.items():
        p = shapes.pending(4, 1, "job")[n]
        p["spec"] = {**p["spec"], "nodeName": f"node-{n}"}
        if nominated:
            p["status"] = {"nominatedNodeName": f"node-{n}"}
        listing.append(p)
    return nodes, listing, {"cfg": cfg, "check_spread": False}


@pytest.mark.parametrize("fillers, preemptors, count, word", [
    ({0: 4, 1: 4, 2: 4, 3: 4}, {}, 0, ""),                    # set-up
    ({0: 1, 1: 1, 2: 4, 3: 4}, {0: True, 1: True}, 0, ""),    # a sound run
    ({0: 1, 1: 1, 2: 4, 3: 4}, {0: True}, 1, "no preemptor bound"),
    ({0: 1, 1: 4, 2: 4, 3: 4}, {0: False}, 1, "nominatedNodeName"),
    ({0: 3, 1: 4, 2: 4, 3: 4}, {}, 1, "1 of its 4 pods evicted"),
])
def test_final_state_counts_each_eviction_for_nothing(fillers, preemptors,
                                                      count, word):
    nodes, listing, ctx = _final(fillers, preemptors)
    bad = ref.final_state(nodes, listing, ctx)
    assert len(bad) == count and all(word in b for b in bad), bad


def test_final_state_sees_a_victim_not_of_lower_priority():
    nodes, listing, ctx = _final({0: 1, 1: 4, 2: 4, 3: 4}, {0: True})
    for p in listing:
        if p["metadata"]["name"].startswith("job"):
            p["spec"] = {**p["spec"], "priority": 10}
    ctx["cfg"] = {**ctx["cfg"], "priority_shapes": [
        {**ctx["cfg"]["priority_shapes"][0], "priority": 10},
        ctx["cfg"]["priority_shapes"][1]]}
    assert any("took the place" in b
               for b in ref.final_state(nodes, listing, ctx))


def _history(victims_a_node: int, warm: bool = False) -> tuple:
    cfg = {**SMALL, "nodes": 4, "existing_pods": 16}
    shapes = priority_fill.Population(cfg, 1, 4)
    nodes = priority_fill.make_nodes(cfg)
    prebound = shapes.prebound(4, 16)
    pods = shapes.pending(2, 1, "job")
    by_name = {p["metadata"]["name"]: p for p in pods}
    history = []
    if warm:   # a throw-away pod preempted on node 3 and left again
        history += [("deleted", f"base-{12 + j}", "") for j in range(3)]
        history += [("bound", "warm0-0000001-g1", "node-3"),
                    ("deleted", "warm0-0000001-g1", "")]
    for n, p in enumerate(pods):
        history += [("deleted", f"base-{4 * n + j}", "")
                    for j in range(victims_a_node)]
        history.append(("bound", p["metadata"]["name"], f"node-{n}"))
    return (nodes, prebound, history, by_name, shapes.samples(),
            {"cfg": cfg, "check_spread": False})


@pytest.mark.parametrize("victims, warm, count", [
    (3, False, 0), (3, True, 0), (4, False, 2), (4, True, 2)])
def test_replay_counts_a_victim_beyond_the_minimum_once_a_node(victims, warm,
                                                               count):
    checked, bad = ref.replay(*_history(victims, warm))
    assert checked == 2
    assert len(bad) == count and all("still fits" in b for b in bad), bad


def test_replay_sees_a_pod_of_the_backlog_deleted():
    args = _history(3)
    name = next(iter(args[3]))
    args[2].append(("deleted", name, ""))
    _checked, bad = ref.replay(*args)
    assert f"{name}: a pod of the run's own backlog was deleted" in bad


def test_the_population_is_the_rule_under_every_seed():
    a = priority_fill.Population(SMALL, 7, 64)
    b = priority_fill.Population(SMALL, 8, 64)
    assert a.prebound(64, 256) == b.prebound(64, 256)
    per_node: dict = {}
    for p in a.prebound(64, 256):
        per_node.setdefault(p["spec"]["nodeName"], []).append(p)
    assert set(map(len, per_node.values())) == {4} and len(per_node) == 64
    assert per_node["node-5"][0]["metadata"]["name"] == "base-20"
    pa, pb = a.pending(64, 7, "job"), b.pending(64, 8, "job")
    assert len(pa) == len(pb) == 64
    assert {p["metadata"]["name"] for p in pa} \
        != {p["metadata"]["name"] for p in pb}
    assert {p["spec"]["priority"] for p in pa} == {10}
    assert a.n == 2 and [a.priority(g) for g in (0, 1)] == [0, 10]
    assert [a.group_of(p) for p in a.samples()] == [0, 1]
    assert ref.final_state(priority_fill.make_nodes(SMALL),
                           a.prebound(64, 256),
                           {"cfg": SMALL, "check_spread": True}) == []


def test_the_cells_plug_ins_resolve_and_its_kind_prebinds_the_population():
    bench = cell.load_json(ROOT, "BENCHMARK.json")
    _cell, cfg, tr = cell.find_cell(bench, "preempt-5k.backlog")
    assert cfg["reduced"] == {} and cfg["nodes"] == 5000
    assert (cfg["existing_pods"], cfg["backlog_pods"]) == (20000, 5000)
    plugs = cell.plug_ins(bench, "per_layer", "preempt-5k.backlog", cfg, tr)
    assert plugs["shapes"] is priority_fill
    assert plugs["kind"] is preempt_backlog
    assert [n for n, _ in plugs["checks"]] == ["placement", "preemption"]
    kind = preempt_backlog.Kind(tr, cfg, 40)
    assert (kind.prebound, kind.work, kind.check_spread) == (20000, 5000,
                                                             False)
    from benchmarks.harness.wirings import local

    d = local.serving_dims(cfg)
    assert (d.N, d.P, d.E) == (5120, 8192, 32768)


def test_field_count_counts_the_waves_that_ran_a_pass():
    waves = [{"preempt_preemptors": 64}, {}, {"preempt_preemptors": 0},
             {"preempt_preemptors": 3}]
    assert field_count.read({"waves": waves},
                            {"field": "preempt_preemptors"}) == 2
    assert field_count.read({"waves": [{}]},
                            {"field": "preempt_preemptors"}) is None


def test_the_what_ifs_roofline_is_reckoned_from_the_capacities_alone():
    dims = {"N": 5120, "P": 8192, "E": 32768, "R": 4, "L": 8, "K": 4,
            "SC": 64}
    spec = {"lanes": 8, "S": 8}
    one = preempt_roofline.whatif_bytes(dims, 8, 8)
    assert one == 8 * (32768 * 17 + 2 * 64 * 5120 * 4 + 2 * 8 * 5120 * 4
                       + 5120 * 8 * 4 + 6 * 5120 * 4)
    waves = [{"preempt_dispatches": 1, "phases": [("requeue", 2.0)]},
             {"phases": [("bind-commit", 1.0)]}]
    obs = {"trace": {"busy_s": 1.0, "idle_gaps": [["requeue", 1.5]]},
           "rehearse": False, "waves": waves, "dims": dims,
           "device": {"kind": "TPU v5 lite"}}
    got = preempt_roofline.read(obs, spec)
    assert got == pytest.approx(100.0 * one / 819e9 / 0.5)
    assert got < 100
    # a CPU, no trace, a program that records no dispatch: nothing
    assert preempt_roofline.read({**obs, "rehearse": True}, spec) is None
    assert preempt_roofline.read({**obs, "trace": None}, spec) is None
    assert preempt_roofline.read(
        {**obs, "waves": [{"phases": [("requeue", 2.0)]}]}, spec) is None


# --------------------------------------------------------------------- #
# the cell rehearsed through benchmarks/run.py, and its controls
# --------------------------------------------------------------------- #

ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}
REHEARSAL_LIMIT_S = 600


def rehearse(command: list) -> tuple:
    done = subprocess.run([sys.executable, *command], cwd=ROOT, env=ENV,
                          capture_output=True, text=True,
                          timeout=REHEARSAL_LIMIT_S)
    lines = done.stdout.splitlines()
    info = [json.loads(ln[5:]) for ln in lines if ln.startswith("info ")]
    results = [json.loads(ln) for ln in lines if ln.startswith("{")]
    assert info and results, done.stderr[-2000:]
    return info[-1], results


def test_the_cell_rehearses_correct_with_three_victims_and_one_pass():
    info, results = rehearse([
        "benchmarks/run.py", "--workload", "preempt-5k.backlog", "--seed",
        "2041000041", "--seconds", "40", "--trace", "1", "--rehearse"])
    res = results[-1]
    assert res["correct"] is True
    assert (res["attempted"], res["failed"]) == (64, 0)
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert {"victims_evicted_for_nothing", "victims_beyond_minimum",
            "bindings_infeasible_at_their_turn",
            "compilations_in_window"} <= set(res["checks"])
    got = {k: m["value"] for k, m in res["metrics"].items()}
    assert got["preempt_victims_per_nomination"] == 3.0
    assert got["preempt_passes"] == 1.0
    bench = cell.load_json(ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in cell.metrics_of(
        bench, "per_layer", "preempt-5k.backlog")}
    # a CPU has no place in the table of peaks: the roofline share alone is
    # left out of a rehearsal's line
    assert listed - set(got) == {"preempt_whatif_roofline_pct"}
    for name in ("preempt_whatif_s", "preempt_evict_ms_per_victim",
                 "preempt_nominate_ms_per_pod", "preempt_pass_s"):
        assert got[name] > 0, name
    assert info["n_waves"] == 2 and info["bound_in_window"] == 64


@pytest.mark.parametrize("control, seed, failed, count", [
    ("skip_reprieve", "20410041", "victims_beyond_minimum", 64),
    ("evict_unhanded_nodes", "20410041", "victims_evicted_for_nothing",
     None),
    ("drop_bindings", "20410048", "pods_never_bound", 2),
])
def test_a_broken_pass_is_not_correct(control, seed, failed, count):
    _info, results = rehearse([
        "benchmarks/tests/chip_control_preempt.py", "--workload",
        "preempt-5k.backlog", "--control", control, "--seeds", seed,
        "--seconds", "15", "--rehearse"])
    run, summary = results[0], results[-1]
    assert summary == {"workload": "preempt-5k.backlog", "control": control,
                       "runs": 1, "not_correct": 1}
    assert run["correct"] is False
    value = run["checks"][failed]["value"]
    assert value == count if count is not None else value > 0
