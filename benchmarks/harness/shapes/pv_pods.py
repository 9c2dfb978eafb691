"""One pod template whose every pod mounts a PersistentVolume of its own,
upstream scheduler_perf's `SchedulingCSIPVs` (`persistentVolumeTemplatePath:
config/pv-csi.yaml`, `persistentVolumeClaimTemplatePath: config/pvc.yaml`):
for a pending pod `<name>` there is ONE claim `pvc-<name>`, pre-bound
(`spec.volumeName`, `status.phase: Bound`, the bind-completed annotation) to
ONE PV `pv-<name>` of the configuration's CSI driver with a volume handle of
its own, no node affinity and no zone label. That is the configuration's
`volume_rule`, which checks/volumes.py reads the same way.

Nodes are `objects.make_nodes`' with the driver's attach limit in their
allocatable (`attachable-volumes-csi-<driver>`) and a CSINode each that says
the same (`extra_objects`, created before any pod). `attach_limit` is one
number for every node; `attach_limits`, where a configuration's `rehearse`
block gives it, is cycled over the nodes, so that a limit can bind at a size
at which an even spread would never reach a uniform one.

The population bound before the scheduler starts is `existing_pods` plain
pods of the same requests, pod i on node i % nodes, under names the seed does
not touch. `pending` makes the volume pods, named from the seed; the PVs and
claims of the backlog and of warm-up's throw-away pods (`warmup` in the
configuration: rounds x pods, which the mix's warm-up has to agree with) are
made from the same names. Every seed is the same work."""

from __future__ import annotations

import random

from .. import objects


def limit_of(cfg: dict, i: int) -> int:
    cycle = cfg.get("attach_limits") or [cfg["attach_limit"]]
    return cycle[i % len(cycle)]


def make_nodes(cfg: dict) -> list:
    key = "attachable-volumes-csi-" + cfg["volume_rule"]["driver"]
    nodes = objects.make_nodes(cfg)
    for i, n in enumerate(nodes):
        n["status"]["allocatable"][key] = str(limit_of(cfg, i))
    return nodes


def csinode(cfg: dict, i: int) -> dict:
    return {"apiVersion": "storage.k8s.io/v1", "kind": "CSINode",
            "metadata": {"name": f"node-{i}"},
            "spec": {"drivers": [{
                "name": cfg["volume_rule"]["driver"], "nodeID": f"node-{i}",
                "topologyKeys": [],
                "allocatable": {"count": limit_of(cfg, i)}}]}}


def volume_pair(cfg: dict, pod_name: str) -> tuple:
    """(PV, claim) of the pod `pod_name`, bound to each other."""
    rule, shape = cfg["volume_rule"], cfg["pv_shape"]
    pv, pvc = rule["pv_prefix"] + pod_name, rule["claim_prefix"] + pod_name
    modes = list(shape["access_modes"])
    return ({"apiVersion": "v1", "kind": "PersistentVolume",
             "metadata": {"name": pv, "annotations": {
                 "pv.kubernetes.io/bound-by-controller": "yes"}},
             "spec": {"accessModes": modes,
                      "capacity": {"storage": shape["storage"]},
                      "persistentVolumeReclaimPolicy": shape["reclaim"],
                      "csi": {"driver": rule["driver"],
                              "volumeHandle": rule["handle_prefix"]
                              + pod_name},
                      "claimRef": {"kind": "PersistentVolumeClaim",
                                   "namespace": "default", "name": pvc}},
             "status": {"phase": "Bound"}},
            {"apiVersion": "v1", "kind": "PersistentVolumeClaim",
             "metadata": {"name": pvc, "namespace": "default", "annotations": {
                 "pv.kubernetes.io/bind-completed": "yes"}},
             "spec": {"accessModes": modes, "volumeName": pv,
                      "resources": {"requests": {
                          "storage": shape["storage"]}}},
             "status": {"phase": "Bound",
                        "capacity": {"storage": shape["storage"]}}})


class Population:
    n = 1

    def __init__(self, cfg: dict, seed: int, work: int):
        self.cfg, self.seed, self.work = cfg, seed, work
        self.shape = cfg["pod_shape"]

    def priority(self, g: int) -> int:
        return 0

    def _pod(self, name: str, claim: str = "", node_name: str = "") -> dict:
        spec = {"schedulerName": "default-scheduler", "priority": 0,
                "containers": [{"name": "pause",
                                "image": "k8s.gcr.io/pause:3.2",
                                "resources": {"requests": {
                                    "cpu": self.shape["cpu"],
                                    "memory": self.shape["memory"]}},
                                "ports": []}]}
        if claim:
            spec["volumes"] = [{"name": "vol", "persistentVolumeClaim": {
                "claimName": claim}}]
        if node_name:
            spec["nodeName"] = node_name
        return {"apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": name, "namespace": "default",
                             "uid": f"default/{name}",
                             "labels": {"shape": "1" if claim else "0"}},
                "spec": spec}

    def _names(self, count: int, seed: int, tag: str) -> list:
        rng = random.Random(seed * 1_000_003 + 29)
        return [f"{tag}-{tok:07d}-g1"
                for tok in rng.sample(range(10 ** 7), count)]

    def pending(self, count: int, seed: int, tag: str) -> list:
        pre = self.cfg["volume_rule"]["claim_prefix"]
        return [self._pod(name, pre + name)
                for name in self._names(count, seed, tag)]

    def prebound(self, n_nodes: int, count: int) -> list:
        return [self._pod(f"base-{i}", node_name=f"node-{i % n_nodes}")
                for i in range(count)]

    @staticmethod
    def group_of(pod: dict) -> int:
        return 0

    def samples(self) -> list:
        return [self._pod("shape-0"), self._pod("shape-1", "pvc-shape-1")]

    def warmup_names(self) -> list:
        warm = self.cfg["warmup"]
        return [name for rnd in range(warm["rounds"])
                for name in self._names(warm["pods"], self.seed,
                                        f"warm{rnd}")]

    def extra_objects(self) -> list:
        out = [("csinodes", csinode(self.cfg, i))
               for i in range(self.cfg["nodes"])]
        for name in self._names(self.work, self.seed, "job") \
                + self.warmup_names():
            pv, pvc = volume_pair(self.cfg, name)
            out += [("persistentvolumes", pv),
                    ("persistentvolumeclaims", pvc)]
        return out
