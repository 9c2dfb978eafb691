"""Seeded generators of the cluster objects a cell sends: nodes and pods as
plain v1 dicts. Nothing here imports the program; it receives only what
these functions return.

Every seed gives the SAME work: the seed permutes which group carries which
role, which request tier each group has, pod names and the order of creation
inside a priority. Counts per role, per tier, per (role, tier) and per
priority are fixed by the configuration alone.
"""

from __future__ import annotations

import random

ZONE = "topology.kubernetes.io/zone"
RACK = "topology.kubernetes.io/rack"
HOSTNAME = "kubernetes.io/hostname"

#: role -> (priority, has host anti-affinity, has in-zone affinity to a partner)
ROLES = {
    "plain": (0, False, False),
    "spread": (0, False, False),
    "anti": (1, True, False),
    "affinity": (2, False, True),
}


def make_nodes(cfg: dict) -> list:
    """`cfg["nodes"]` nodes in `zones` x `racks_per_zone`, node i in zone
    i % zones (the shape of models/workloads.make_nodes)."""
    zones, racks = cfg["zones"], cfg["racks_per_zone"]
    alloc = {"cpu": cfg["node_cpu"], "memory": cfg["node_memory"],
             "ephemeral-storage": "0Ki", "pods": str(cfg["node_pods"])}
    out = []
    for i in range(cfg["nodes"]):
        z = i % zones
        r = (i // zones) % racks
        out.append({
            "apiVersion": "v1", "kind": "Node",
            "metadata": {"name": f"node-{i}", "labels": {
                ZONE: f"zone-{z}", RACK: f"zone-{z}-rack-{r}",
                HOSTNAME: f"node-{i}"}},
            "spec": {},
            "status": {"allocatable": dict(alloc), "images": []},
        })
    return out


def _selector(app: str) -> dict:
    return {"matchExpressions": [
        {"key": "app", "operator": "In", "values": [app]}]}


class Groups:
    """The deployment groups of one configuration under one seed: for each
    group its role, priority, request tier and (for an affinity group) its
    anti-affinity partner. `pod(g, token, ...)` stamps one pod of group g."""

    def __init__(self, cfg: dict, seed: int, replicas_per_group: int):
        rng = random.Random(seed * 1_000_003 + 17)
        n = cfg["groups"]
        order = list(range(n))
        rng.shuffle(order)
        self.n = n
        self.role: dict = {}
        self.partner: dict = {}
        self.tier: dict = {}
        tiers = cfg["request_tiers"]
        start = 0
        by_role = {}
        for role, count in cfg["roles"].items():
            if role not in ROLES:
                raise ValueError(f"unknown role {role!r}")
            members = order[start:start + count]
            start += count
            by_role[role] = members
            for i, g in enumerate(members):
                self.role[g] = role
                # tiers cycle inside a role: the (role, tier) table is the
                # same for every seed
                self.tier[g] = tiers[i % len(tiers)]
        if start != n:
            raise ValueError("roles do not add up to groups")
        for i, g in enumerate(by_role.get("affinity", ())):
            self.partner[g] = by_role["anti"][i]
        # flagship_pods: maxSkew = max(2, replicas // 8)
        self.max_skew = max(2, replicas_per_group // 8) \
            if cfg.get("zone_spread") else 0
        self._templates = {g: self._template(g) for g in range(n)}

    def priority(self, g: int) -> int:
        return ROLES[self.role[g]][0]

    def _template(self, g: int) -> dict:
        app = f"app-{g}"
        _prio, anti, aff = ROLES[self.role[g]]
        cpu, mem = self.tier[g]
        spec = {
            "schedulerName": "default-scheduler",
            "priority": self.priority(g),
            "containers": [{"name": "main", "image": "registry/app:v1",
                            "resources": {"requests": {"cpu": cpu,
                                                       "memory": mem}},
                            "ports": []}],
        }
        affinity = {}
        if anti:
            affinity["podAntiAffinity"] = {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {"labelSelector": _selector(app),
                     "topologyKey": HOSTNAME}]}
        if aff:
            affinity["podAffinity"] = {
                "requiredDuringSchedulingIgnoredDuringExecution": [
                    {"labelSelector": _selector(f"app-{self.partner[g]}"),
                     "topologyKey": ZONE}]}
        if affinity:
            spec["affinity"] = affinity
        if self.max_skew:
            spec["topologySpreadConstraints"] = [{
                "maxSkew": self.max_skew, "topologyKey": ZONE,
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": _selector(app)}]
        return spec

    def pod(self, g: int, name: str, node_name: str = "") -> dict:
        """One pod of group g. A fresh dict per pod (the apiserver keeps what
        it is given); the spec's nested parts are shared, read-only."""
        spec = dict(self._templates[g])
        if node_name:
            spec["nodeName"] = node_name
        return {"apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": name, "namespace": "default",
                             "uid": f"default/{name}",
                             "labels": {"app": f"app-{g}"}},
                "spec": spec}


def pending_pods(groups: Groups, count: int, seed: int, tag: str) -> list:
    """`count` pods (a multiple of the group count), the same number from
    every group, named from the seed, in an order the seed shuffles. The
    apiserver's list is by name and the scheduler's queue is by priority
    then arrival, so the seed decides the order inside a priority."""
    if count % groups.n:
        raise ValueError(f"{count} pods do not divide into {groups.n} groups")
    rng = random.Random(seed * 1_000_003 + 29)
    per = count // groups.n
    slots = [g for g in range(groups.n) for _ in range(per)]
    rng.shuffle(slots)
    tokens = rng.sample(range(10 ** 7), count)
    return [groups.pod(g, f"{tag}-{tok:07d}-g{g}")
            for g, tok in zip(slots, tokens)]


def prebound_pods(groups: Groups, n_nodes: int, count: int) -> list:
    """The existing population, already bound by a fixed rule: replica j of
    group g sits on node (g * 97 + j * stride) % n_nodes with an odd stride,
    so a group's replicas are on distinct nodes (its host anti-affinity), walk
    every zone evenly (its zone spread, skew <= 1), and every zone holds each
    partner (in-zone affinity). The reference's invariant checker verifies
    it in set-up."""
    if count % groups.n:
        raise ValueError(f"{count} pods do not divide into {groups.n} groups")
    per = count // groups.n
    if not per:
        return []
    if per > n_nodes:
        raise ValueError("more replicas per group than nodes: host "
                         "anti-affinity cannot hold")
    stride = max(n_nodes // per, 1)
    if stride % 2 == 0:
        stride -= 1
    out = []
    for g in range(groups.n):
        for j in range(per):
            node = (g * 97 + j * stride) % n_nodes
            out.append(groups.pod(g, f"base-g{g}-{j}", f"node-{node}"))
    return out
