"""A pod's volumes on the served path (ISSUE 45): API objects in, through
`Client.local`, the informers, the volume binder, the encoder's private /
shared split and the device's volume plane, against the benchmark's plain
reference (benchmarks/harness/checks/volumes.py, which imports nothing of the
program). No hand-built `Pod(volumes=...)` in the served cases: every
VolumeRef comes from `pod_from_v1` or a claim the binder followed.
"""

import json
import os
import random
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks.harness import cell
from benchmarks.harness.checks import volumes as ref
from benchmarks.harness.shapes import pv_pods
from benchmarks.harness.sources import volume_roofline
from kubernetes_tpu.api.types import ClaimRef, VolumeRef
from kubernetes_tpu.api.v1 import (csinode_volume_limits, node_from_v1,
                                   pod_from_v1, volume_ref_from_pv)
from kubernetes_tpu.apiserver import APIServer
from kubernetes_tpu.client import Client
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.sched.server import APIBinder, SchedulerServer
from kubernetes_tpu.state.dims import Dims
from kubernetes_tpu.state.encode import Encoder
from kubernetes_tpu.volume.pv_controller import PersistentVolumeController

ROOT = cell.ROOT
BENCH = cell.load_json(ROOT, "BENCHMARK.json")
CFG = cell.load_json(ROOT, "benchmarks", "configs", "csi-pvs-5k.json")
CELL = "csi-pvs-5k.backlog"
CSI = "ebs.csi.aws.com"
ZONE = "topology.kubernetes.io/zone"
#: one compiled program for every served case below
BASE = Dims(N=16, P=32, E=64, F=16, SC=32, SN=32, VS=4, SV=16, VW=1, DR=4)


# --------------------------------------------------------------------- #
# API objects
# --------------------------------------------------------------------- #

def node(i: int, zone: str, limit=None, extra=None) -> dict:
    alloc = {"cpu": "16", "memory": "64Gi", "pods": "110", **(extra or {})}
    if limit is not None:
        alloc["attachable-volumes-csi-" + CSI] = str(limit)
    return {"apiVersion": "v1", "kind": "Node",
            "metadata": {"name": f"n{i}", "labels": {
                ZONE: zone, "kubernetes.io/hostname": f"n{i}"}},
            "spec": {}, "status": {"allocatable": alloc}}


def csinode(i: int, count: int) -> dict:
    return {"apiVersion": "storage.k8s.io/v1", "kind": "CSINode",
            "metadata": {"name": f"n{i}"},
            "spec": {"drivers": [{"name": CSI, "nodeID": f"n{i}",
                                  "allocatable": {"count": count}}]}}


def pv(name: str, handle: str, zone: str = "", claim: str = "",
       sc: str = "", affinity_zone: str = "") -> dict:
    out = {"apiVersion": "v1", "kind": "PersistentVolume",
           "metadata": {"name": name, "labels": {ZONE: zone} if zone else {}},
           "spec": {"accessModes": ["ReadWriteOnce"],
                    "capacity": {"storage": "1Gi"},
                    "csi": {"driver": CSI, "volumeHandle": handle}},
           "status": {"phase": "Bound" if claim else "Available"}}
    if sc:
        out["spec"]["storageClassName"] = sc
    if claim:
        out["spec"]["claimRef"] = {"kind": "PersistentVolumeClaim",
                                   "namespace": "default", "name": claim}
    if affinity_zone:
        out["spec"]["nodeAffinity"] = {"required": {"nodeSelectorTerms": [
            {"matchExpressions": [{"key": ZONE, "operator": "In",
                                   "values": [affinity_zone]}]}]}}
    return out


def pvc(name: str, volume: str = "", sc: str = "") -> dict:
    out = {"apiVersion": "v1", "kind": "PersistentVolumeClaim",
           "metadata": {"name": name, "namespace": "default"},
           "spec": {"accessModes": ["ReadWriteOnce"],
                    "resources": {"requests": {"storage": "1Gi"}}},
           "status": {"phase": "Bound" if volume else "Pending"}}
    if volume:
        out["spec"]["volumeName"] = volume
    if sc:
        out["spec"]["storageClassName"] = sc
    return out


def pod(name: str, vols: list) -> dict:
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default",
                         "uid": f"default/{name}"},
            "spec": {"schedulerName": "default-scheduler",
                     "containers": [{"name": "c", "image": "img:v1",
                                     "resources": {"requests": {
                         "cpu": "100m", "memory": "64Mi"}}}],
                     "volumes": [{"name": f"v{i}", **v}
                                 for i, v in enumerate(vols)]}}


def claim_vol(name: str) -> dict:
    return {"persistentVolumeClaim": {"claimName": name}}


def gce(name: str, ro: bool) -> dict:
    return {"gcePersistentDisk": {"pdName": name, "readOnly": ro}}


def ebs(name: str) -> dict:
    return {"awsElasticBlockStore": {"volumeID": name}}


# --------------------------------------------------------------------- #
# decode: VolumeRefs and limits from API objects
# --------------------------------------------------------------------- #

def test_a_pods_volumes_and_a_nodes_limits_are_decoded_from_v1():
    p = pod_from_v1(pod("p", [claim_vol("c1"), gce("d", True), ebs("e"),
                              {"emptyDir": {}},
                              {"rbd": {"pool": "k", "image": "i"}}]))
    assert p.claims == (ClaimRef("c1", False),)
    assert p.volumes == (
        VolumeRef("d", "kubernetes.io/gce-pd", True),
        VolumeRef("e", "kubernetes.io/aws-ebs", False),   # never shared
        VolumeRef("k/i", "kubernetes.io/rbd", False))
    assert pod_from_v1(pod("q", [])).volumes == ()
    n = node_from_v1(node(0, "a", 39,
                          {"attachable-volumes-aws-ebs": "25",
                           "example.com/gpu": "2"}))
    assert n.volume_limits == {CSI: 39, "kubernetes.io/aws-ebs": 25}
    # a limit is no extended resource: R does not grow with it
    assert n.allocatable.scalars == (("example.com/gpu", 2),)
    assert csinode_volume_limits(csinode(0, 7)) == {CSI: 7}
    assert volume_ref_from_pv(pv("pv", "h")) == VolumeRef("h", CSI, True)
    assert volume_ref_from_pv({"spec": {"nfs": {"path": "/"}}}) is None


# --------------------------------------------------------------------- #
# the representation: a volume of one pod alone splits no class
# --------------------------------------------------------------------- #

def own_volume_pods(n: int, start: int = 0) -> list:
    out = []
    for i in range(start, start + n):
        p = pod_from_v1(pod(f"p{i}", []))
        p.volumes = (volume_ref_from_pv(pv(f"pv{i}", f"vol-{i}")),)
        out.append(p)
    return out


def test_500_pods_with_a_claim_each_are_one_class_and_grow_no_dims():
    enc = Encoder()
    plain = pod_from_v1(pod("plain", []))
    enc.intern_pods([plain])
    before = enc.dims(8, 8, 8, [])
    pods = own_volume_pods(500)
    enc.intern_pods(pods)
    assert len({enc.pod_row(p)[2] for p in pods}) == 1
    assert len(enc.class_reg) == 2 and not enc.classes_stale
    after = enc.dims(8, 8, 8, [])
    assert after == before   # no field follows the number of such volumes
    assert (after.SC, after.SV, after.VW, after.VS) == (8, 4, 1, 2)
    assert len(enc.vocabs.volumes) == 0 and len(enc.vol_owner) == 500
    t = enc.build_class_table(after)
    cid = enc.pod_row(pods[0])[2]
    did = enc.vocabs.vol_drivers.get(CSI)
    assert t.volset[cid] == -1 and t.vol_priv[cid, did] == 1


def test_a_second_pod_naming_a_volume_promotes_it_for_every_pod():
    enc = Encoder()
    a, b = own_volume_pods(2)
    enc.intern_pods([a, b])
    assert enc.pod_row(a)[2] == enc.pod_row(b)[2]
    c = pod_from_v1(pod("c", []))
    c.volumes = a.volumes   # a second pod names a's volume
    enc.intern_pods([c])
    assert enc.classes_stale   # class ids made before are stale: re-walk
    enc.projection_rewalk()
    enc.intern_pods([a, b, c])
    assert not enc.classes_stale
    assert enc.pod_row(a)[2] == enc.pod_row(c)[2] != enc.pod_row(b)[2]
    assert len(enc.vocabs.volumes) == 1
    assert (CSI, "vol-0") not in enc.vol_owner
    enc.release_volumes(b)
    assert (CSI, "vol-1") not in enc.vol_owner


def one_node_scheduler(limit: int):
    from kubernetes_tpu.sched.scheduler import RecordingBinder

    sched = Scheduler(binder=RecordingBinder(), base_dims=BASE)
    sched.queue.initial_backoff = sched.queue.max_backoff = 0.01
    sched.on_node_add(node_from_v1(node(0, "a", limit)))
    return sched


def resident(sched) -> tuple:
    snap = sched.cache.snapshot(sched.encoder, [], sched.base_dims)
    n = snap.tables.nodes
    return int(np.asarray(n.vol_cnt)[0].sum()), int(np.asarray(n.vol_any)[0, 0])


def test_pods_of_one_wave_on_one_node_see_each_others_volumes():
    # one class, five pods, one node that may hold three volumes: the
    # in-wave sum of the counts stops the fourth
    sched = one_node_scheduler(3)
    for p in own_volume_pods(5):
        sched.on_pod_add(p)
    stats = sched.run_until_idle(max_waves=3)
    assert stats.scheduled == 3 and resident(sched) == (3, 0)


def test_a_pod_deleted_gives_its_nodes_count_back_and_forget_does_too():
    sched = one_node_scheduler(2)
    first = own_volume_pods(2)
    for p in first:
        sched.on_pod_add(p)
    assert sched.run_until_idle(max_waves=2).scheduled == 2
    assert resident(sched) == (2, 0)
    third, = own_volume_pods(1, start=2)
    sched.on_pod_add(third)
    assert sched.run_until_idle(max_waves=2).scheduled == 0   # full
    gone = sched.cache.get_pod(first[0].key)
    sched.on_pod_delete(gone)
    assert resident(sched) == (1, 0)
    assert (CSI, "vol-0") not in sched.encoder.vol_owner
    time.sleep(0.05)   # the third pod's backoff
    assert sched.run_until_idle(max_waves=3).scheduled == 1
    assert resident(sched) == (2, 0)
    # assume / forget: the count follows the assumed pod out
    sched.cache.forget_assumed()
    kept = sum(1 for p in first[1:] + [third]
               if sched.cache.get_pod(p.key) is not None)
    assert resident(sched) == (kept, 0)


# --------------------------------------------------------------------- #
# the served path against the plain reference, on seeded random clusters
# --------------------------------------------------------------------- #

def wait_for(cond, timeout=40.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


class Served:
    def __init__(self):
        self.api = APIServer()
        self.client = Client.local(self.api)
        self.server = None

    def start(self):
        sched = Scheduler(binder=APIBinder(self.client), batch_size=BASE.P,
                          base_dims=BASE)
        sched.queue.initial_backoff = sched.queue.max_backoff = 0.05
        self.server = SchedulerServer(self.client, scheduler=sched,
                                      cycle_interval=0.02, batch_window=0.02)
        self.server.start()
        return self

    def listing(self) -> tuple:
        c = self.client
        objs = {"pvs": c.persistentvolumes.list()["items"],
                "pvcs": c.persistentvolumeclaims.list("default")["items"],
                "csinodes": c.csinodes.list()["items"]}
        return (c.nodes.list()["items"], c.pods.list("default")["items"],
                {"volumes": objs})

    def close(self):
        if self.server is not None:
            self.server.stop()
        self.api.close()


def random_cluster(rng: random.Random) -> tuple:
    """(objects by resource, pods, the pods that must wait). Limits of 1-3
    refuse nodes; CSI PVs are shared by two pods or one pod's own, some
    zonal by label, some by node affinity; GCE disks are shared read-only
    and read-write; an EBS volume is named twice; WaitForFirstConsumer
    claims have free zonal PVs; an Immediate claim has none yet."""
    zones = ["a", "b", "c"]
    n_nodes = rng.randint(6, 9)
    nodes, csinodes = [], []
    for i in range(n_nodes):
        lim = rng.randint(1, 3)
        if rng.random() < 0.5:   # the CSINode's count wins over allocatable
            nodes.append(node(i, zones[i % 3], rng.randint(4, 9)))
            csinodes.append(csinode(i, lim))
        else:
            nodes.append(node(i, zones[i % 3], lim))
    pvs, pvcs, pods = [], [], []
    scs = [{"apiVersion": "storage.k8s.io/v1", "kind": "StorageClass",
            "metadata": {"name": "wffc"},
            "provisioner": "kubernetes.io/no-provisioner",
            "volumeBindingMode": "WaitForFirstConsumer"}]
    for i in range(rng.randint(8, 14)):   # bound claims
        zone = rng.choice(zones) if rng.random() < 0.5 else ""
        by_label = rng.random() < 0.5
        pvs.append(pv(f"pv-{i}", f"h-{i}", zone if by_label else "",
                      f"c-{i}", affinity_zone="" if by_label else zone))
        pvcs.append(pvc(f"c-{i}", f"pv-{i}"))
        pods.append(pod(f"bound-{i}", [claim_vol(f"c-{i}")]))
        if rng.random() < 0.3:   # a second pod on the same claim
            pods.append(pod(f"bound-{i}-twin", [claim_vol(f"c-{i}")]))
    for i in range(rng.randint(2, 4)):   # WaitForFirstConsumer
        zone = rng.choice(zones)
        pvs.append(pv(f"free-{i}", f"f-{i}", sc="wffc", affinity_zone=zone))
        pvcs.append(pvc(f"w-{i}", sc="wffc"))
        pods.append(pod(f"wffc-{i}", [claim_vol(f"w-{i}")]))
    for i in range(rng.randint(2, 5)):   # a disk shared read-only
        pods.append(pod(f"ro-{i}", [gce("shared-ro", True)]))
    for i in range(rng.randint(2, 3)):   # read-write: one a node
        pods.append(pod(f"rw-{i}", [gce("shared-rw", i > 0)]))
    pods += [pod("ebs-0", [ebs("vol-x")]), pod("ebs-1", [ebs("vol-x")])]
    pods += [pod(f"plain-{i}", []) for i in range(3)]
    pvcs.append(pvc("late"))   # Immediate, unbound: the pod waits
    pods.append(pod("waits", [claim_vol("late")]))
    rng.shuffle(pods)
    return ({"nodes": nodes, "csinodes": csinodes, "storageclasses": scs,
             "persistentvolumes": pvs, "persistentvolumeclaims": pvcs},
            pods, {"waits"})


def settled(served: Served, waiting: set) -> tuple:
    """(done, why not): every pod is bound, or waits on its claim, or the
    reference refuses it on every node beside what is bound there."""
    nodes, pods, ctx = served.listing()
    world = ref.World(nodes, ref.Volumes(ctx))
    for p in pods:
        if p["spec"].get("nodeName"):
            world.place(p, p["spec"]["nodeName"])
    for p in pods:
        name = p["metadata"]["name"]
        if p["spec"].get("nodeName") or name in waiting:
            continue
        free = [n["metadata"]["name"] for n in nodes
                if not world.refusals(p, n["metadata"]["name"])]
        if free:
            return False, f"{name} is unbound and {free} would take it"
    return True, ""


@pytest.mark.parametrize("seed", range(12))
def test_the_served_path_agrees_with_the_plain_reference(seed):
    rng = random.Random(1000 + seed)
    objects, pods, waiting = random_cluster(rng)
    served = Served()
    try:
        c = served.client
        for resource, items in objects.items():
            for obj in items:
                getattr(c, resource).create(obj)
        # some pods are there before the scheduler, the rest arrive
        split = len(pods) // 2
        for p in pods[:split]:
            c.pods.create(p)
        served.start()
        for p in pods[split:]:
            c.pods.create(p)
        assert wait_for(lambda: settled(served, waiting)[0]), \
            settled(served, waiting)[1]
        nodes, listed, ctx = served.listing()
        found = ref.counts(nodes, listed, ctx)
        assert {k: v for k, v in found.items() if v} == {}
        by_name = {p["metadata"]["name"]: p for p in listed}
        # the limit refused nodes: volume pods stayed off nodes with room
        # for their cpu, or the cluster's limits were never reached
        bound = [p for p in listed if p["spec"].get("nodeName")]
        assert len(bound) >= 10
        # every WaitForFirstConsumer claim was bound at placement, to a PV
        # its pod's node reaches (`counts` above held the topology)
        for name, p in by_name.items():
            if name.startswith("wffc-") and p["spec"].get("nodeName"):
                claim = c.persistentvolumeclaims.get(
                    "w-" + name[len("wffc-"):], "default")
                assert claim["spec"].get("volumeName"), name
        # the unbound Immediate claim parked its pod
        assert not by_name["waits"]["spec"].get("nodeName")
        assert "default/waits" in served.server._waiting_on_volumes
        # the scheduler's own state adds up, node by node
        want = ref.attached_counts(nodes, listed, ctx)
        snap = served.server.scheduler.cache.snapshot(
            served.server.scheduler.encoder, [], BASE)
        words = np.asarray(snap.tables.nodes.vol_any)
        have = np.unpackbits(words.view(np.uint8), axis=-1).sum(-1) \
            + np.asarray(snap.tables.nodes.vol_cnt).sum(-1)
        assert {n: int(have[i]) for i, n in enumerate(snap.node_order)
                if n} == want
        # the claim binds: the pod lands, if a node has room for a volume
        late_pv = pv("pv-late", "h-late")
        c.persistentvolumes.create(late_pv)
        PersistentVolumeController.bind(
            c, late_pv, c.persistentvolumeclaims.get("late", "default"))
        assert wait_for(lambda: settled(served, set())[0]), \
            settled(served, set())[1]
        nodes, listed, ctx = served.listing()
        assert {k: v for k, v in ref.counts(nodes, listed, ctx).items()
                if v} == {}
        waits = next(p for p in listed if p["metadata"]["name"] == "waits")
        if waits["spec"].get("nodeName"):
            assert not served.server._waiting_on_volumes
    finally:
        served.close()


def test_a_limit_refuses_nodes_and_the_pods_stay_off_them():
    # two nodes that may hold one volume each, four pods with a claim each:
    # two bind, two stay pending, and the reference finds no node for them
    served = Served()
    try:
        c = served.client
        for i in range(2):
            c.nodes.create(node(i, "a", 5))
            c.csinodes.create(csinode(i, 1))
        for i in range(4):
            c.persistentvolumes.create(pv(f"pv-{i}", f"h-{i}",
                                          claim=f"c-{i}"))
            c.persistentvolumeclaims.create(pvc(f"c-{i}", f"pv-{i}"))
            c.pods.create(pod(f"p-{i}", [claim_vol(f"c-{i}")]))
        served.start()
        assert wait_for(lambda: sum(
            1 for p in c.pods.list("default")["items"]
            if p["spec"].get("nodeName")) == 2)
        time.sleep(0.5)
        nodes, listed, ctx = served.listing()
        assert sum(1 for p in listed if p["spec"].get("nodeName")) == 2
        assert settled(served, set())[0]
        assert not ref.final_state(nodes, listed, ctx)
        rec = [r for r in served.server.scheduler.telemetry.recorder.records()
               if r.get("volume_pods")]
        assert rec and rec[0]["volume_classes"] == 1
        assert rec[0]["volumes_distinct"] == rec[0]["volume_pods"]
    finally:
        served.close()


def test_volume_binding_off_leaves_all_of_it_off():
    served = Served()
    try:
        c = served.client
        c.nodes.create(node(0, "a", 1))
        c.persistentvolumeclaims.create(pvc("late"))
        c.pods.create(pod("p", [claim_vol("late")]))
        server = SchedulerServer(c, base_dims=BASE, volume_binding=False,
                                 cycle_interval=0.02, batch_window=0.02)
        served.server = server.start()
        assert server.pvc_informer is None and server.volume_binder is None
        assert wait_for(lambda: c.pods.get("p", "default")["spec"]
                        .get("nodeName"))
    finally:
        served.close()


# --------------------------------------------------------------------- #
# the benchmark's parts without a run
# --------------------------------------------------------------------- #

def test_the_cell_names_its_modules_and_they_are_there():
    _cell, cfg, tr = cell.find_cell(BENCH, CELL)
    plugs = cell.plug_ins(BENCH, "per_layer", CELL, cfg, tr)
    assert plugs["shapes"] is pv_pods
    assert [n for n, _m in plugs["checks"]] == ["placement", "volumes"]
    assert plugs["wiring"].__name__.endswith("wirings.local_pv")
    assert plugs["kind"].__name__.endswith("kinds.pv_backlog")
    from benchmarks.harness.wirings import local, local_pv

    d = local.serving_dims(cfg)
    assert (d.N, d.P, d.E, d.SC) == (5120, 2048, 8192, 64)
    # nothing is provisioned for a number of volumes
    assert "dims" not in cfg
    assert (d.SV, d.VW, d.VS, d.DR) == (Dims().SV, Dims().VW, Dims().VS,
                                        Dims().DR)
    assert issubclass(local_pv.Cluster, local.Cluster)
    assert cfg["reduced"] == {} and cfg["backlog_pods"] == 2000


def test_the_shape_makes_one_pv_and_one_bound_claim_a_pod_whatever_the_seed():
    cfg = {**CFG, **CFG["rehearse"]}
    counts = set()
    for seed in (5, 2 ** 31 + 7):
        pop = pv_pods.Population(cfg, seed, cfg["backlog_pods"])
        pods = pop.pending(cfg["backlog_pods"], seed, "job")
        extra = pop.extra_objects()
        kinds = [r for r, _o in extra]
        counts.add((len(pods), kinds.count("csinodes"),
                    kinds.count("persistentvolumes"),
                    kinds.count("persistentvolumeclaims")))
        claims = {o["metadata"]["name"]: o for r, o in extra
                  if r == "persistentvolumeclaims"}
        pvs = {o["metadata"]["name"]: o for r, o in extra
               if r == "persistentvolumes"}
        for p in pods:
            name, = [v["persistentVolumeClaim"]["claimName"]
                     for v in p["spec"]["volumes"]]
            assert name == "pvc-" + p["metadata"]["name"]
            claim = claims[name]
            assert claim["status"]["phase"] == "Bound"
            bound = pvs[claim["spec"]["volumeName"]]
            assert bound["spec"]["claimRef"]["name"] == name
            assert bound["spec"]["csi"]["driver"] == CSI
            assert "nodeAffinity" not in bound["spec"]
        # the rule the check reads is the rule the shape wrote
        vols = ref.Volumes({"cfg": cfg})
        got = vols.pv_of_claim("default", "pvc-" + pods[0]["metadata"]["name"])
        assert got["spec"]["csi"] == pvs[got["metadata"]["name"]][
            "spec"]["csi"]
    warm = cfg["warmup"]["rounds"] * cfg["warmup"]["pods"]
    assert counts == {(100, 64, 100 + warm, 100 + warm)}
    nodes = pv_pods.make_nodes(cfg)
    limits = [n["status"]["allocatable"]["attachable-volumes-csi-" + CSI]
              for n in nodes]
    assert limits[:4] == ["1", "3", "1", "3"]
    assert {n["status"]["allocatable"]["attachable-volumes-csi-" + CSI]
            for n in pv_pods.make_nodes(CFG)} == {"39"}
    assert len(pop.prebound(64, 64)) == 64
    assert not ref.final_state(nodes, pop.prebound(64, 64), {"cfg": cfg})


def listing_with(on_node: dict) -> tuple:
    nodes = [node(0, "a", 1), node(1, "b", 2)]
    pods = []
    for name, (where, vols) in on_node.items():
        p = pod(name, vols)
        p["spec"]["nodeName"] = where
        pods.append(p)
    objs = {"pvs": [pv("pv-0", "h-0", zone="a", claim="c-0"),
                    pv("pv-1", "h-1", claim="c-1"),
                    pv("pv-2", "h-2", claim="c-2")],
            "pvcs": [pvc("c-0", "pv-0"), pvc("c-1", "pv-1"),
                     pvc("c-2", "pv-2"), pvc("open")],
            "csinodes": []}
    return nodes, pods, {"volumes": objs}


@pytest.mark.parametrize("on_node, count", [
    ({"a": ("n0", [claim_vol("c-0")]), "b": ("n0", [claim_vol("c-1")])},
     "nodes_over_volume_limit"),
    ({"a": ("n1", [gce("d", False)]), "b": ("n1", [gce("d", True)])},
     "volume_conflicts"),
    ({"a": ("n1", [ebs("e")]), "b": ("n1", [ebs("e")])}, "volume_conflicts"),
    ({"a": ("n1", [claim_vol("c-0")])}, "pods_bound_off_their_pv_topology"),
    ({"a": ("n1", [claim_vol("open")])}, "pods_bound_with_unbound_claims"),
    ({"a": ("n1", [claim_vol("nowhere")])},
     "pods_bound_with_unbound_claims"),
])
def test_the_reference_sees_each_violation(on_node, count):
    nodes, pods, ctx = listing_with(on_node)
    found = ref.counts(nodes, pods, ctx)
    assert [k for k, v in found.items() if v] == [count]
    assert len(found[count]) == 1
    # and at the Binding's turn, on the same history
    by_name = {p["metadata"]["name"]: {**p, "spec": {
        k: v for k, v in p["spec"].items() if k != "nodeName"}}
        for p in pods}
    history = [("bound", p["metadata"]["name"], p["spec"]["nodeName"])
               for p in pods]
    looked, bad = ref.replay(nodes, [], history, by_name, [], ctx)
    assert looked == len(pods) and len(bad) == 1


def test_the_reference_counts_a_shared_volume_once_and_allows_read_only():
    nodes, pods, ctx = listing_with({
        "a": ("n1", [claim_vol("c-1")]), "b": ("n1", [claim_vol("c-1")]),
        "c": ("n1", [claim_vol("c-2"), gce("d", True)]),
        "d": ("n1", [gce("d", True)])})
    assert not ref.final_state(nodes, pods, ctx)
    assert ref.attached_counts(nodes, pods, ctx) == {"n0": 0, "n1": 3}


def test_the_volume_roofline_reader_counts_the_planes_state():
    dims = {"N": 5120, "P": 2048, "E": 8192, "R": 4, "L": 8, "K": 4,
            "SC": 64}
    spec = {"kind": "volume_roofline", "DR": 2, "VW": 1}
    assert volume_roofline.volume_bytes(dims, 2, 1) == 4 * (
        3 * 5120 * 2 + 4 * 5120 + 64 * 2)
    obs = {"trace": {"busy_s": 0.01, "window_s": 4.0}, "rehearse": False,
           "dims": dims, "device": {"kind": "TPU v5 lite"},
           "waves": [{"device_split": {"execute_s": 0.01}, "volume_pods": 9},
                     {"device_split": {"execute_s": 0.01}}]}
    got = volume_roofline.read(obs, spec)
    assert 0 < got < 100
    # a parent's record has no `volume_pods`: nothing, and no error
    assert volume_roofline.read(
        {**obs, "waves": [{"device_split": {}}]}, spec) is None
    assert volume_roofline.read({**obs, "rehearse": True}, spec) is None
    assert volume_roofline.read({**obs, "trace": None}, spec) is None


@pytest.mark.parametrize("name", [
    "start_volumes_sync_s", "volume_resolve_ms_per_pod",
    "volume_bind_ms_per_pod", "volume_classes_first",
    "volume_engine_roofline_pct"])
def test_each_new_metric_has_its_file_and_lists_the_cell(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "drain_pods_per_s"
    spec = cell.load_json(cell.BENCH_DIR, "metrics", name + ".json")
    assert spec["layer"] == entry["layer"]
    cell.plug_in("sources", spec["source"]["kind"])


# --------------------------------------------------------------------- #
# the cell rehearsed through benchmarks/run.py, and its controls
# --------------------------------------------------------------------- #

ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def rehearse(command: list) -> tuple:
    done = subprocess.run([sys.executable, *command], cwd=ROOT, env=ENV,
                          capture_output=True, text=True, timeout=600)
    lines = done.stdout.splitlines()
    info = [json.loads(ln[5:]) for ln in lines if ln.startswith("info ")]
    results = [json.loads(ln) for ln in lines if ln.startswith("{")]
    assert info and results, done.stderr[-2000:]
    return info[-1], results


def test_the_cell_rehearses_correct_as_one_class_in_one_wave():
    info, results = rehearse([
        "benchmarks/run.py", "--workload", CELL, "--seed", "2045000041",
        "--seconds", "40", "--trace", "1", "--rehearse"])
    res = results[-1]
    assert res["correct"] is True
    assert (res["attempted"], res["failed"]) == (100, 0)
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert {"node_volume_state_wrong", "compilations_in_window",
            "volume_bindings_refused_at_their_turn", *ref.COUNTS} <= set(
        res["checks"])
    got = {k: m["value"] for k, m in res["metrics"].items()}
    assert got["volume_classes_first"] == 1.0
    listed = {m["name"] for m in cell.metrics_of(BENCH, "per_layer", CELL)}
    # a CPU has no place in the table of peaks
    assert listed - set(got) == {"volume_engine_roofline_pct"}
    for name in ("start_volumes_sync_s", "volume_resolve_ms_per_pod",
                 "volume_bind_ms_per_pod"):
        assert got[name] > 0, name
    assert info["n_waves"] == 1 and info["bound_in_window"] == 100
    assert info["volumes_attached"] == 100
    assert info["dims"]["SC"] == 64


@pytest.mark.parametrize("control, failed, sound", [
    ("ignore_volumes", ("node_volume_state_wrong", "nodes_over_volume_limit",
                        "volume_bindings_refused_at_their_turn"), ()),
    ("ignore_volume_limits", ("nodes_over_volume_limit",
                              "volume_bindings_refused_at_their_turn"),
     ("node_volume_state_wrong",)),
])
def test_a_scheduler_that_ignores_volumes_is_not_correct(control, failed,
                                                         sound):
    _info, results = rehearse([
        "benchmarks/tests/chip_control_volumes.py", "--workload", CELL,
        "--control", control, "--seeds", "2045000048", "--seconds", "20",
        "--rehearse"])
    run, summary = results[0], results[-1]
    assert summary == {"workload": CELL, "control": control, "runs": 1,
                       "not_correct": 1}
    assert run["correct"] is False
    for name in failed:
        assert run["checks"][name]["value"] > 0, name
    for name in sound:
        assert run["checks"][name]["value"] == 0, name
