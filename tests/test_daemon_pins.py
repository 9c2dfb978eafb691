"""A pod's pin (ISSUE 49): the one node its required node affinity names by
`matchFields metadata.name` on every term, as the DaemonSet controller writes
it, is the pod's own datum and not part of its class.

Held here: the class counts; the engines (waves, the `scan` spec, the
extender's `filter`) against `api/semantics.py` on seeded clusters with
cordoned, tainted and full nodes; the served path with the controller creating
the pods; a pinned preemptor; the benchmark's shape against the controller,
its plain reference (benchmarks/harness/checks/daemons.py, which imports
nothing of the program), its readers, and the cell's rehearsal with its
controls.
"""

import json
import os
import random
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from benchmarks.harness import cell
from benchmarks.harness.checks import daemons as ref
from benchmarks.harness.shapes import daemon_pods
from benchmarks.harness.sources import pin_roofline
from kubernetes_tpu.api import semantics
from kubernetes_tpu.api.types import (Affinity, Node, NodeSelector,
                                      NodeSelectorTerm, Op, Pod, Requirement,
                                      Resources, Taint, TaintEffect,
                                      Toleration, TolerationOp)
from kubernetes_tpu.api.v1 import pod_from_v1, pod_to_v1
from kubernetes_tpu.sched.cycle import (UNSCHEDULABLE_TAINT_KEY, _feasible,
                                        _schedule_batch)
from kubernetes_tpu.state.dims import Dims
from kubernetes_tpu.state.encode import Encoder, pin_name, without_pin

ROOT = cell.ROOT
BENCH = cell.load_json(ROOT, "BENCHMARK.json")
CFG = cell.load_json(ROOT, "benchmarks", "configs", "daemonset-5k.json")
SMALL = {**CFG, **CFG["rehearse"]}
CELL = "daemonset-5k.backlog"
OS = "kubernetes.io/os"
CORDON = "node.kubernetes.io/unschedulable"
TOLERATE_ALL = (Toleration(key=CORDON, op=TolerationOp.EXISTS,
                           effect=TaintEffect.NO_SCHEDULE),)
#: one Dims bucket for every engine case below: ONE compiled program each
BASE = Dims(N=16, P=64, E=32, SC=16, SN=16, F=2, TL=4, TT=2, L=4)


def name_term(*names, exprs=()):
    return NodeSelectorTerm(tuple(exprs), tuple(names))


def pinned(name, node, cpu="100m", exprs=(), terms=1, **kw) -> Pod:
    """A pod as the controller writes it: `terms` terms, each naming
    `node`."""
    aff = Affinity(node_required=NodeSelector(
        tuple(name_term(node, exprs=exprs) for _ in range(terms))))
    return Pod(name=name, affinity=aff, tolerations=TOLERATE_ALL,
               requests=Resources.make(cpu=cpu, memory="64Mi"), **kw)


LINUX = (Requirement(OS, Op.IN, ("linux",)),)


# --------------------------------------------------------------------- #
# the encoding: a DaemonSet is ONE class
# --------------------------------------------------------------------- #

def test_the_pin_is_the_one_name_on_every_term():
    assert pin_name(pinned("p", "n1").affinity) == "n1"
    assert pin_name(pinned("p", "n1", terms=3, exprs=LINUX).affinity) == "n1"
    assert pin_name(Affinity()) == ""
    two_names = Affinity(node_required=NodeSelector((name_term("a", "b"),)))
    differ = Affinity(node_required=NodeSelector(
        (name_term("a"), name_term("b"))))
    one_without = Affinity(node_required=NodeSelector(
        (name_term("a"), NodeSelectorTerm(LINUX))))
    empty = Affinity(node_required=NodeSelector((NodeSelectorTerm(),)))
    for aff in (two_names, differ, one_without, empty):
        assert pin_name(aff) == ""
    # a term left empty matches every node once the pin holds: no required
    # node affinity at all; one with expressions keeps them, fields gone
    assert without_pin(pinned("p", "n1").affinity).node_required is None
    mixed = Affinity(node_required=NodeSelector(
        (name_term("n1", exprs=LINUX), name_term("n1"))))
    assert without_pin(mixed).node_required is None
    kept = without_pin(pinned("p", "n1", exprs=LINUX, terms=2).affinity)
    assert kept.node_required == NodeSelector(
        (NodeSelectorTerm(LINUX), NodeSelectorTerm(LINUX)))


@pytest.mark.parametrize("n,exprs,classes,nterms", [
    (1, (), 1, 0), (500, (), 1, 0), (500, LINUX, 1, 1)])
def test_a_daemonset_of_n_pods_is_one_class(n, exprs, classes, nterms):
    enc = Encoder()
    pods = [pinned(f"ds-{i}", f"node-{i}", exprs=exprs) for i in range(n)]
    enc.intern_pods(pods)
    rows = [enc.pod_row(p) for p in pods]
    assert len({r[2] for r in rows}) == classes == len(enc.class_reg)
    assert len(enc.nterm_reg) == nterms
    names = enc.vocabs.node_names
    assert [names.lookup(r[6]) for r in rows] == [f"node-{i}"
                                                  for i in range(n)]
    # nothing is provisioned for a number of pinned pods
    d = enc.dims(8, 1, n, [])
    assert (d.SC, d.SN, d.STL, d.F) == (Dims().SC, Dims().SN, Dims().STL,
                                        Dims().F)
    # pod_row (one pod at a time) and intern_pods (the batch) agree
    fresh = Encoder()
    assert [fresh.pod_row(p)[2:] for p in pods[:3]] == [r[2:]
                                                        for r in rows[:3]]


def test_a_pinned_and_an_unpinned_pod_of_one_spec_are_two_classes():
    enc = Encoder()
    plain = Pod(name="plain", tolerations=TOLERATE_ALL,
                requests=Resources.make(cpu="100m", memory="64Mi"))
    enc.intern_pods([plain, pinned("p", "n1")])
    assert len(enc.class_reg) == 2
    assert enc.pod_row(plain)[6] == -1


@pytest.mark.parametrize("terms", [
    [("a", "b")], [("a",), ("b",)], [("a",), ()]])
def test_other_uses_of_matchfields_stay_on_the_field_path(terms):
    """Several names in a term, names that differ between terms, a term
    without one: no pin, the fields stay in the term table, a class a pod."""
    enc = Encoder()
    pods = []
    for i in range(4):
        ts = tuple(NodeSelectorTerm(() if t else LINUX,
                                    tuple(f"{x}{i}" for x in t))
                   for t in terms)
        pods.append(Pod(name=f"p{i}", affinity=Affinity(
            node_required=NodeSelector(ts))))
    enc.intern_pods(pods)
    assert len(enc.class_reg) == 4
    assert all(enc.pod_row(p)[6] == -1 for p in pods)
    fields = [enc.nterm_reg.lookup(i)[1] for i in range(len(enc.nterm_reg))]
    assert sum(len(f) for f in fields) == 4 * sum(len(t) for t in terms)


# --------------------------------------------------------------------- #
# the engines against api/semantics.py
# --------------------------------------------------------------------- #

def seeded_cluster(seed: int) -> tuple:
    """Nodes: cordoned (with the cordon's taint), tainted otherwise, full,
    linux and windows. Pods: four DaemonSets over every node (plain; with
    an os expression; on two terms; one that tolerates nothing), pods pinned
    to a node that is not there, plain pods and pods on the field path.
    Room is ample or none, so which pods land is one answer."""
    rng = random.Random(seed)
    nodes, existing = [], []
    for i in range(rng.randint(8, 12)):
        kind = rng.choice(["plain", "plain", "cordoned", "tainted", "full",
                           "windows"])
        taints, uns = (), False
        if kind == "cordoned":
            uns = True
            taints = (Taint(CORDON, "", TaintEffect.NO_SCHEDULE),)
        elif kind == "tainted":
            taints = (Taint("dedicated", "ml", TaintEffect.NO_SCHEDULE),)
        nodes.append(Node(
            name=f"n{i}", unschedulable=uns, taints=taints,
            labels={OS: "windows" if kind == "windows" else "linux",
                    "kubernetes.io/hostname": f"n{i}"},
            allocatable=Resources.make(cpu="8", memory="16Gi", pods=110)))
        if kind == "full":
            existing.append(Pod(
                name=f"fill-{i}", node_name=f"n{i}",
                requests=Resources.make(cpu="8", memory="1Gi")))
    pending, idx = [], 0
    for n in nodes + [Node(name="gone")]:
        for ds, kw in (("plain", {}), ("os", {"exprs": LINUX}),
                       ("two", {"exprs": LINUX, "terms": 2})):
            pending.append(pinned(f"{ds}-{n.name}", n.name,
                                  creation_index=idx, **kw))
            idx += 1
        strict = pinned(f"strict-{n.name}", n.name, creation_index=idx)
        strict.tolerations = ()
        pending.append(strict)
        idx += 1
    for j in range(3):
        pending.append(Pod(name=f"free-{j}", creation_index=idx + j,
                           requests=Resources.make(cpu="100m",
                                                   memory="64Mi")))
    a, b = rng.sample(nodes, 2)
    pending.append(Pod(name="either", creation_index=idx + 3,
                       requests=Resources.make(cpu="100m", memory="64Mi"),
                       affinity=Affinity(node_required=NodeSelector(
                           (name_term(a.name, b.name),)))))
    rng.shuffle(pending)
    return nodes, existing, pending


def statically_fits(pod: Pod, node: Node) -> bool:
    return (semantics.pod_matches_node_selector(pod, node)
            and semantics.pod_tolerates_node_taints(pod, node)
            and semantics.check_node_unschedulable(pod, node))


def run_engines(nodes, existing, pending) -> dict:
    enc = Encoder()
    enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
    enc.vocabs.label_vals.intern("")
    tables, ex, pe, d = enc.encode_cluster(nodes, existing, pending, BASE)
    assert (d.SC, d.SN) == (BASE.SC, BASE.SN)
    assert not d.has_node_name
    import jax.numpy as jnp

    keys = (jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY)),
            jnp.int32(enc.vocabs.label_vals.get("")))
    out = {}
    for engine in ("waves", "scan"):
        res = _schedule_batch(tables, pe, keys, d.D, ex, engine=engine)
        out[engine] = np.asarray(res.node)[:len(pending)]
        if engine == "waves":
            out["rounds"] = int(res.rounds)
    out["mask"] = np.asarray(_feasible(tables, pe, keys, d.D, ex))
    return out


@pytest.mark.parametrize("seed", range(8))
def test_waves_scan_and_filter_agree_with_the_semantics(seed):
    nodes, existing, pending = seeded_cluster(seed)
    got = run_engines(nodes, existing, pending)
    full = {p.node_name for p in existing}
    for i, pod in enumerate(pending):
        want = [statically_fits(pod, n) and n.name not in full
                for n in nodes]
        # the Filter row (the extender's `filter` reads this one)
        assert list(got["mask"][i, :len(nodes)]) == want, pod.name
        assert not got["mask"][i, len(nodes):].any()
        for engine in ("waves", "scan"):
            at = got[engine][i]
            assert (at >= 0) == any(want), (engine, pod.name)
            assert at < 0 or want[at], (engine, pod.name)
        pin = pin_name(pod.affinity)
        if pin:
            # on its pin or nowhere, and the two engines say the same
            where = {n.name: j for j, n in enumerate(nodes)}.get(pin, -1)
            assert got["waves"][i] in (-1, where)
            assert got["waves"][i] == got["scan"][i]
    # every DaemonSet's pods whose nodes pass are admitted in ONE round
    # (the second: a zero-progress round fails the plain pods' leftovers)
    assert got["rounds"] <= 2


def test_two_pods_of_a_class_pinned_to_one_node_take_a_round_each():
    node = Node(name="n0", allocatable=Resources.make(
        cpu="1", memory="1Gi", pods=110))
    pods = [pinned(f"twin-{i}", "n0", cpu="400m", creation_index=i,
                   priority=10 - i) for i in range(3)]
    got = run_engines([node], [], pods)
    # room for two: the two ahead in the queue, one a round
    assert list(got["waves"]) == [0, 0, -1] == list(got["scan"])
    assert got["rounds"] == 3


def test_the_extenders_filter_answers_the_pin_or_none():
    from kubernetes_tpu.extender.backend import ExtenderBackend
    from kubernetes_tpu.extender.wire import ExtenderArgs

    nodes, existing, _ = seeded_cluster(3)
    be = ExtenderBackend()
    be.sync_nodes(nodes)
    be.sync_scheduled_pods(existing)
    names = [n.name for n in nodes]
    full = {p.node_name for p in existing}
    for n in nodes:
        res = be.filter(ExtenderArgs(
            pod=pod_to_v1(pinned(f"ds-{n.name}", n.name, exprs=LINUX)),
            node_names=names))
        ok = n.labels[OS] == "linux" and n.name not in full \
            and not any(t.key == "dedicated" for t in n.taints)
        assert res.node_names == ([n.name] if ok else []), n.name
        assert set(res.failed_nodes) == set(names) - set(res.node_names)


# --------------------------------------------------------------------- #
# the scheduler: record, metrics, preemption
# --------------------------------------------------------------------- #

def small_scheduler(**kw):
    from kubernetes_tpu.sched.scheduler import RecordingBinder, Scheduler

    s = Scheduler(binder=RecordingBinder(), clock=lambda: 0.0,
                  base_dims=Dims(N=16, P=32, E=64), **kw)
    return s


def test_a_wave_with_pins_says_so_on_its_record_and_counters():
    from kubernetes_tpu.sched import metrics

    s = small_scheduler()
    for i in range(4):
        s.on_node_add(Node(name=f"n{i}", allocatable=Resources.make(
            cpu="1", memory="1Gi", pods=110)))
    s.on_pod_add(Pod(name="hog", node_name="n3",
                     requests=Resources.make(cpu="1", memory="64Mi")))
    for i in range(4):
        s.on_pod_add(pinned(f"a-{i}", f"n{i}", creation_index=i))
        s.on_pod_add(pinned(f"b-{i}", f"n{i}", cpu="200m",
                            creation_index=10 + i))
    s.on_pod_add(Pod(name="plain", creation_index=99,
                     requests=Resources.make(cpu="100m", memory="64Mi")))
    fit0 = metrics.PINNED_PODS.value(result="fit")
    unfit0 = metrics.PINNED_PODS.value(result="unfit")
    st = s.schedule_pending()
    assert (st.scheduled, st.unschedulable) == (7, 2)
    assert (st.pinned, st.pin_classes, st.pinned_unfit) == (8, 2, 2)
    rec = s.telemetry.recorder.records()[-1]
    assert (rec["pinned"], rec["pin_classes"], rec["pinned_unfit"]) \
        == (8, 2, 2)
    assert rec["classes"] == len(s.encoder.class_reg) == 4
    assert rec["pin_rounds"] == 1
    assert "snapshot/pins" in rec["children"]
    # the server's loop hands every wave's stats to the counters
    metrics.observe_wave(st, s.queue.depths(), s.cache.counts()[:2])
    assert metrics.PINNED_PODS.value(result="fit") - fit0 == 6
    assert metrics.PINNED_PODS.value(result="unfit") - unfit0 == 2
    assert metrics.PIN_CLASSES.value() == 2
    for key, node in s.binder.bound:
        assert key.endswith("plain") or key.split("-")[-1] == node[1:]
    # a wave without a pin adds none of the fields
    s.on_pod_add(Pod(name="later", creation_index=100,
                     requests=Resources.make(cpu="100m", memory="64Mi")))
    s.schedule_pending()
    rec = s.telemetry.recorder.records()[-1]
    assert not {"pinned", "pin_classes", "pinned_unfit", "classes",
                "pin_rounds"} & set(rec)
    assert "snapshot/pins" in rec["children"]


def test_a_pinned_preemptor_evicts_on_its_pin_alone():
    from kubernetes_tpu.sched.preemption import Preemptor

    s = small_scheduler(preemptor=Preemptor())
    for i in range(4):
        s.on_node_add(Node(name=f"n{i}", allocatable=Resources.make(
            cpu="1", memory="1Gi", pods=110)))
        # n0's pods are the CHEAPEST victims anywhere: an unpinned
        # preemptor would be sent there
        for j in range(2):
            s.on_pod_add(Pod(
                name=f"low-{i}-{j}", node_name=f"n{i}", creation_index=i,
                priority=0 if i == 0 else 5,
                requests=Resources.make(cpu="500m", memory="64Mi")))
    vips = [pinned(f"vip-{i}", f"n{i}", cpu="600m", priority=100,
                   creation_index=50 + i) for i in (2, 3)]
    for p in vips:
        s.on_pod_add(p)
    st = s.schedule_pending()
    assert st.scheduled == 0
    assert {p.key: s.queue.nominated_node(p.key) for p in vips} == {
        "default/vip-2": "n2", "default/vip-3": "n3"}
    left = {p.key for p in s.cache.scheduled_pods()}
    gone = {f"default/low-{i}-{j}" for i in range(4) for j in range(2)} - left
    assert {k.split("-")[1] for k in gone} == {"2", "3"}
    assert s.preemptor.last_pass["preempt_lanes"] == 2
    st = s.schedule_pending()
    assert st.scheduled == 2
    assert dict(s.binder.bound) == {"default/vip-2": "n2",
                                    "default/vip-3": "n3"}


# --------------------------------------------------------------------- #
# the served path: the controller writes the pods, the scheduler binds them
# --------------------------------------------------------------------- #

def wait_for(cond, timeout=60.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def strip(pod: dict) -> dict:
    """A pod without what names it or the apiserver stamps on it."""
    meta = {k: v for k, v in pod["metadata"].items()
            if k in ("namespace", "labels", "annotations", "ownerReferences")}
    return {"apiVersion": pod.get("apiVersion"), "kind": pod.get("kind"),
            "metadata": meta, "spec": {k: v for k, v in pod["spec"].items()
                                       if k != "nodeName"}}


def test_the_controller_writes_the_shapes_pod_and_the_scheduler_binds_it():
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.controllers.manager import ControllerManager
    from kubernetes_tpu.sched.scheduler import Scheduler
    from kubernetes_tpu.sched.server import APIBinder, SchedulerServer

    cfg = {**SMALL, "nodes": 8, "cordoned": {"every": 4, "offset": 1,
                                             "nodes": 2},
           "full": {"every": 8, "offset": 6, "nodes": 1},
           "backlog_pods": 28, "waiting_pods": 4, "existing_pods": 1}
    api = APIServer()
    client = Client.local(api)
    pop = daemon_pods.Population(cfg, 7, cfg["backlog_pods"])
    for n in daemon_pods.make_nodes(cfg):
        client.nodes.create(n)
    for p in pop.prebound(cfg["nodes"], 1):
        client.pods.create(p)
    sched = Scheduler(binder=APIBinder(client), batch_size=64,
                      base_dims=Dims(N=8, P=64, E=64))
    sched.queue.initial_backoff = sched.queue.max_backoff = 0.05
    server = SchedulerServer(client, scheduler=sched, cycle_interval=0.02,
                             batch_window=0.02)
    cm = ControllerManager(client, controllers=["daemonset"])
    # what the controller SENDS, before the apiserver's defaulting
    sent, create = {}, client.pods.create

    def recording_create(obj, *args, **kw):
        out = create(obj, *args, **kw)
        sent[out["metadata"]["name"]] = obj
        return out

    client.pods.create = recording_create
    server.start()
    cm.start()
    try:
        for resource, ds in pop.extra_objects():
            getattr(client, resource).create(ds)

        def owned():
            return [p for p in client.pods.list("default")["items"]
                    if ref.owner_daemonset(p)]

        def settled():
            pods = owned()
            return len(pods) == 32 and sum(
                1 for p in pods if p["spec"].get("nodeName")) == 28

        assert wait_for(settled), [
            (p["metadata"]["name"], p["spec"].get("nodeName"))
            for p in owned()]
        nodes = client.nodes.list()["items"]
        pods = client.pods.list("default")["items"]
        found = ref.counts(nodes, pods, {"cfg": cfg})
        assert {k: len(v) for k, v in found.items()} == dict.fromkeys(
            ref.COUNTS, 0), found
        by_ds = {ds["metadata"]["name"]: ds
                 for ds in client.daemonsets.list("default")["items"]}
        pending = set()
        for p in owned():
            pin = ref.pin_of(p)
            # the shape's pod equals the controller's, field for field
            owner = by_ds[ref.owner_daemonset(p)]
            assert strip(sent[p["metadata"]["name"]]) == strip(
                daemon_pods.daemon_pod(owner, p["metadata"]["name"], pin))
            at = p["spec"].get("nodeName")
            if pin == "node-6":      # the full node refuses: an Event says
                assert not at
                pending.add(p["metadata"]["name"])
            else:                    # cordoned node-1 and node-5 included
                assert at == pin
        assert len(pending) == 4

        def told():
            return pending <= {
                e["involvedObject"]["name"]
                for e in client.events.list("default")["items"]
                if e["reason"] == "FailedScheduling"}

        assert wait_for(told, timeout=20)
        # the log-agent's TEMPLATE carries the os expression; its pods do
        # not (the controller replaces the terms), so it is a class like
        # the others: four DaemonSets, four classes
        rows = {sched.encoder.pod_row(pod_from_v1(p))[2] for p in owned()}
        assert len(rows) == 4
    finally:
        cm.stop()
        server.stop()
        api.close()


# --------------------------------------------------------------------- #
# the benchmark's side: shape, reference, readers, the cell
# --------------------------------------------------------------------- #

def test_the_cell_names_its_modules_and_they_are_there():
    c, cfg, tr = cell.find_cell(BENCH, CELL)
    assert (c["config"], c["traffic"], c["chips"]) == (
        "daemonset-5k", "daemonset-restart-backlog", 1)
    plugs = cell.plug_ins(BENCH, "per_layer", CELL, cfg, tr)
    assert plugs["shapes"].__name__.endswith("shapes.daemon_pods")
    assert plugs["kind"].__name__.endswith("kinds.daemon_backlog")
    assert plugs["wiring"].__name__.endswith("wirings.local_daemons")
    assert [n for n, _ in plugs["checks"]] == ["placement", "daemons"]
    conf = next(x for x in BENCH["configs"] if x["name"] == "daemonset-5k")
    assert conf["reduced"] == [] and cfg["reduced"] == {}
    assert len(conf["source"]) <= 200 and conf["source"] == cfg["source"]


def test_the_shape_is_the_same_work_whatever_the_seed():
    work = SMALL["backlog_pods"]
    seen = []
    for seed in (1, 2 ** 31 + 5):
        pop = daemon_pods.Population(SMALL, seed, work)
        pend = pop.pending(work, seed, "job")
        wait = pop.waiting(seed, "job")
        warm = pop.pending(pop.n * 8, seed, "warm0")
        assert (len(pend), len(wait), len(warm)) == (252, 4, 32)
        names = [p["metadata"]["name"] for p in pend + wait + warm]
        assert len(set(names)) == len(names)
        full = {f"node-{i}" for i in pop.full}
        assert {ref.pin_of(p) for p in wait} == full
        assert not {ref.pin_of(p) for p in pend + warm} & full
        seen.append((sorted((ref.owner_daemonset(p), ref.pin_of(p))
                            for p in pend), names))
    assert seen[0][0] == seen[1][0] and seen[0][1] != seen[1][1]
    nodes = daemon_pods.make_nodes(SMALL)
    assert sum(1 for n in nodes if n["spec"].get("unschedulable")) == 2
    assert all(n["metadata"]["labels"][OS] == "linux" for n in nodes)
    with pytest.raises(SystemExit):
        daemon_pods.Population({**SMALL, "waiting_pods": 5}, 1, work)
    # the published size states its own counts
    big = daemon_pods.Population(CFG, 1, CFG["backlog_pods"])
    assert (big.n * len(big.open), big.n * len(big.full)) == (19800, 200)


def listing(**change) -> tuple:
    """Two nodes (n1 cordoned), one DaemonSet's two pods bound on their
    pins, a filler beside; `change` rewrites one thing."""
    nodes = daemon_pods.make_nodes({**SMALL, "nodes": 2, "cordoned": {
        "every": 2, "offset": 1, "nodes": 1}})
    ds = daemon_pods.daemonset(SMALL["daemonsets"][0])
    pods = [daemon_pods.daemon_pod(ds, f"d{i}", f"node-{i}")
            for i in range(2)]
    for i, p in enumerate(pods):
        p["spec"]["nodeName"] = f"node-{i}"
    filler = {"metadata": {"name": "filler"}, "spec": {
        "nodeName": "node-0", "containers": [{"resources": {"requests": {
            "cpu": change.get("filler_cpu", "1000m"), "memory": "1Ki"}}}]}}
    if "move" in change:
        pods[0]["spec"]["nodeName"] = change["move"]
    if change.get("unbind"):
        del pods[1]["spec"]["nodeName"]
    if change.get("intolerant"):
        pods[1]["spec"]["tolerations"] = []
    if change.get("twin"):
        twin = daemon_pods.daemon_pod(ds, "twin", "node-0")
        twin["spec"]["nodeName"] = "node-0"
        pods.append(twin)
    if change.get("stray"):
        filler["spec"]["nodeName"] = "node-1"
    return nodes, pods + [filler]


@pytest.mark.parametrize("change,count", [
    ({}, None),
    ({"move": "node-1"}, "pinned_elsewhere"),
    ({"twin": True}, "pinned_elsewhere"),
    ({"filler_cpu": "32000m"}, "daemon_on_full_node"),
    ({"intolerant": True}, "daemon_on_full_node"),
    ({"stray": True}, "daemon_on_full_node"),
    ({"unbind": True}, "daemon_missing"),
])
def test_the_reference_sees_each_violation(change, count):
    nodes, pods = listing(**change)
    found = {k: len(v) for k, v in ref.counts(nodes, pods, {}).items()}
    assert set(found) == set(ref.COUNTS)
    assert {k for k, v in found.items() if v} == ({count} if count
                                                  else set()), found
    assert ref.final_state(nodes, pods, {}) == ref.counts(
        nodes, pods, {})["pinned_elsewhere"]


def test_a_pod_pending_for_a_full_or_an_intolerable_node_is_not_missing():
    nodes, pods = listing(unbind=True, intolerant=True)
    assert not ref.counts(nodes, pods, {})["daemon_missing"]
    nodes, pods = listing(filler_cpu="32000m")
    del pods[0]["spec"]["nodeName"]
    assert not any(ref.counts(nodes, pods, {}).values())


def test_the_pin_roofline_reader_counts_the_pins_bytes():
    dims = {"N": 5120, "P": 20480, "E": 32768, "R": 4, "L": 8, "K": 4,
            "SC": 64}
    from benchmarks.harness import roofline

    obs = {"trace": {"busy_s": 0.02}, "rehearse": False, "dims": dims,
           "device": {"kind": "TPU v5 lite"},
           "waves": [{"device_split": {"execute_s": 0.01}, "pinned": 9},
                     {"device_split": {"execute_s": 0.01}}]}
    want = 100.0 * (roofline.cycle_bytes(dims) + 4 * (20480 + 5120)
                    + 2 * 64 * 5120) / roofline.peaks(
                        "TPU v5 lite")["hbm_bytes_per_s"] / 0.02
    assert pin_roofline.read(obs, {}) == pytest.approx(want)
    assert 0 < want < 100
    # a parent's record has no `pinned`: nothing, and no error
    obs["waves"] = [{"device_split": {"execute_s": 0.01}}]
    assert pin_roofline.read(obs, {}) is None
    assert pin_roofline.read({**obs, "trace": None}, {}) is None


NEW_METRICS = ["pinned_pods_first", "pin_classes_first", "pin_rounds_first",
               "pin_snapshot_first_s", "pin_engine_roofline_pct"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_each_new_metric_has_its_file_and_lists_the_cell(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "drain_pods_per_s"
    spec = cell.load_json(ROOT, "benchmarks", "metrics", name + ".json")
    assert spec["name"] == name and spec["layer"] == entry["layer"]
    cell.plug_in("sources", spec["source"]["kind"])


def test_the_new_metrics_read_the_record_and_a_parents_gives_nothing():
    rec = {"pinned": 20000, "pin_classes": 4, "pin_rounds": 1,
           "children": {"snapshot/pins": [1, 0.0004, 0.0004]},
           "phases": [], "device_split": {"execute_s": 0.01}}
    obs = {"waves": [rec], "series": {}, "memory": {},
           "bound_in_window": 19800, "window_s": 9.0, "rehearse": True,
           "device": {"kind": "cpu"}, "dims": {}, "trace": None}
    bench = {"per_layer": [m for m in BENCH["per_layer"]
                           if m["name"] in NEW_METRICS]}
    got = cell.compute_metrics(bench, "per_layer", CELL, obs)
    assert {k: v["value"] for k, v in got.items()} == {
        "pinned_pods_first": 20000.0, "pin_classes_first": 4.0,
        "pin_rounds_first": 1.0, "pin_snapshot_first_s": 0.0004}
    obs["waves"] = [{"phases": [], "device_split": {"execute_s": 0.01}}]
    assert cell.compute_metrics(bench, "per_layer", CELL, obs) == {}


def rehearse(command: list) -> tuple:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    p = subprocess.run([sys.executable] + command, cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    return p, [json.loads(ln) for ln in lines]


def test_the_cell_rehearses_correct_in_one_wave_of_four_classes():
    p, lines = rehearse(["benchmarks/run.py", "--workload", CELL, "--seed",
                         str(2 ** 31 + 11), "--seconds", "40", "--trace",
                         "1", "--rehearse"])
    res = lines[-1]
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert res["attempted"] == 252
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["pinned_pods_first"] == 256 and got["pin_classes_first"] == 4
    assert got["pin_rounds_first"] == 1 and "pin_snapshot_first_s" in got
    info = json.loads(next(ln for ln in p.stdout.splitlines()
                           if ln.startswith("info "))[5:])
    assert info["n_waves"] == 1 and info["dims"]["SC"] == 64
    assert (info["daemon_pods_bound"], info["daemon_pods_pending"]) \
        == (252, 4)
    assert res["checks"]["compilations_in_window"]["value"] == 0


@pytest.mark.parametrize("control,failed", [
    ("ignore_pins", "pinned_elsewhere"),
    ("drop_daemon_tolerations", "daemon_missing"),
])
def test_a_scheduler_that_ignores_pins_or_tolerations_is_not_correct(
        control, failed):
    _p, lines = rehearse([
        "benchmarks/tests/chip_control_daemons.py", "--workload", CELL,
        "--control", control, "--seeds", "23", "--seconds", "40",
        "--rehearse"])
    run, summary = lines[-2], lines[-1]
    assert summary["not_correct"] == 1 and not run["correct"]
    assert run["checks"][failed]["value"] > 0, run["checks"]
    if control == "drop_daemon_tolerations":
        # the 2 cordoned nodes' 8 pods, and those alone
        assert run["checks"]["daemon_missing"]["value"] == 8
        assert run["checks"]["pods_never_bound"]["value"] == 8
