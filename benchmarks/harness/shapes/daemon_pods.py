"""DaemonSet pods as the DaemonSet controller writes them since
ScheduleDaemonSetPods (GA in v1.17): for every DaemonSet of the configuration
and every node ONE pod from the DaemonSet's template, with

  * the owner reference of its DaemonSet (`extra_objects` creates the
    DaemonSets themselves, so the references point at objects that exist);
  * required node affinity REPLACED by the one term `matchFields
    metadata.name In [<node>]` (daemonset_util.go
    ReplaceDaemonSetPodNodeNameNodeAffinity: whatever required terms the
    template had are gone from the pod; the controller held them against
    the node itself before it created the pod);
  * the daemon toleration set appended (AddOrUpdateDaemonPodTolerations:
    not-ready and unreachable NoExecute; memory-pressure, disk-pressure and
    unschedulable NoSchedule).

`kubernetes_tpu/controllers/workloads.py DaemonSetController` writes exactly
this; tests/test_daemon_pins.py holds the two equal field for field. Names
are the seed's (the controller's come from generateName).

Nodes are `objects.make_nodes`' with the configuration's `node_labels` on
every one. `cordoned`: node i with i % every == offset carries
`spec.unschedulable` and the `node.kubernetes.io/unschedulable:NoSchedule`
taint the node lifecycle controller adds; daemon pods tolerate it and land
there. `full`: node i with i % every == offset holds one bound filler pod
(`prebound`) that asks the node's whole CPU, so no daemon pod fits: the daemon
pods named for those nodes are `waiting`, at the apiserver with the others
and never to be bound. The two counts the configuration states are verified.

Every seed is the same work: which nodes are cordoned or full, every count
and every request are the configuration's; the seed names the pods and
shuffles the order in which they were created."""

from __future__ import annotations

import random

from .. import objects

UNSCHEDULABLE = "node.kubernetes.io/unschedulable"
#: AddOrUpdateDaemonPodTolerations, in the controller's order
DAEMON_TOLERATIONS = (
    ("node.kubernetes.io/not-ready", "NoExecute"),
    ("node.kubernetes.io/unreachable", "NoExecute"),
    ("node.kubernetes.io/memory-pressure", "NoSchedule"),
    ("node.kubernetes.io/disk-pressure", "NoSchedule"),
    (UNSCHEDULABLE, "NoSchedule"),
)


def _marked(cfg: dict, what: str) -> list:
    """The node indices the rule `cfg[what]` marks, verified against the
    count it states."""
    rule = cfg[what]
    out = [i for i in range(cfg["nodes"])
           if i % rule["every"] == rule["offset"]]
    if len(out) != rule["nodes"]:
        raise SystemExit(f"shapes daemon_pods: the rule {rule} marks "
                         f"{len(out)} of {cfg['nodes']} nodes {what}")
    return out


def make_nodes(cfg: dict) -> list:
    nodes = objects.make_nodes(cfg)
    for n in nodes:
        n["metadata"]["labels"].update(cfg.get("node_labels") or {})
    for i in _marked(cfg, "cordoned"):
        nodes[i]["spec"] = {"unschedulable": True, "taints": [
            {"key": UNSCHEDULABLE, "effect": "NoSchedule"}]}
    return nodes


def daemonset(ds: dict) -> dict:
    """The DaemonSet object of one entry of the configuration's
    `daemonsets`: its template is what the controller stamps pods from."""
    spec = {"schedulerName": "default-scheduler", "priority": 0,
            "containers": [{"name": "agent",
                            "image": f"registry/{ds['name']}:v1",
                            "resources": {"requests": {
                                "cpu": ds["cpu"], "memory": ds["memory"]}},
                            "ports": []}]}
    if ds.get("node_affinity"):
        spec["affinity"] = {"nodeAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": {
                "nodeSelectorTerms": [{"matchExpressions": [
                    dict(ds["node_affinity"])]}]}}}
    labels = {"app": ds["name"]}
    return {"apiVersion": "apps/v1", "kind": "DaemonSet",
            "metadata": {"name": ds["name"], "namespace": "default",
                         "uid": f"daemonset-{ds['name']}"},
            "spec": {"selector": {"matchLabels": dict(labels)},
                     "template": {"metadata": {"labels": dict(labels)},
                                  "spec": spec}}}


def daemon_pod(owner: dict, name: str, node: str) -> dict:
    """The pod the controller creates from `owner`'s template for `node`."""
    tmpl = owner["spec"]["template"]
    spec = dict(tmpl["spec"])
    affinity = {k: dict(v) for k, v in (spec.get("affinity") or {}).items()}
    affinity.setdefault("nodeAffinity", {})[
        "requiredDuringSchedulingIgnoredDuringExecution"] = {
            "nodeSelectorTerms": [{"matchFields": [{
                "key": "metadata.name", "operator": "In",
                "values": [node]}]}]}
    spec["affinity"] = affinity
    spec["tolerations"] = list(spec.get("tolerations") or ()) + [
        {"key": key, "operator": "Exists", "effect": effect}
        for key, effect in DAEMON_TOLERATIONS]
    meta = owner["metadata"]
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": meta["namespace"],
                         "uid": f"{meta['namespace']}/{name}",
                         "labels": dict(tmpl["metadata"]["labels"]),
                         "annotations": {},
                         "ownerReferences": [{
                             "apiVersion": owner["apiVersion"],
                             "kind": owner["kind"], "name": meta["name"],
                             "uid": meta["uid"], "controller": True,
                             "blockOwnerDeletion": True}]},
            "spec": spec}


class Population:
    def __init__(self, cfg: dict, seed: int, work: int):
        self.cfg = cfg
        self.sets = [daemonset(ds) for ds in cfg["daemonsets"]]
        self.n = len(self.sets)
        self.full = _marked(cfg, "full")
        full = set(self.full)
        self.open = [i for i in range(cfg["nodes"]) if i not in full]
        must, must_not = self.n * len(self.open), self.n * len(self.full)
        if (must, must_not, len(self.full)) != (
                cfg["backlog_pods"], cfg["waiting_pods"],
                cfg["existing_pods"]):
            raise SystemExit(
                f"shapes daemon_pods: {self.n} DaemonSets over "
                f"{cfg['nodes']} nodes of which {len(self.full)} are full "
                f"give {must} pods that must bind, {must_not} that must not "
                f"and {len(self.full)} fillers; the configuration states "
                f"backlog_pods {cfg['backlog_pods']}, waiting_pods "
                f"{cfg['waiting_pods']}, existing_pods "
                f"{cfg['existing_pods']}")

    def priority(self, g: int) -> int:
        return 0

    def _stamp(self, slots: list, seed: int, tag: str, skip: int = 0) -> list:
        """One pod for every (DaemonSet, node index) of `slots`, named from
        seed and tag, in an order the seed shuffles. `skip` tokens of the
        seed's draw are another call's: the two name no pod alike."""
        rng = random.Random(seed * 1_000_003 + 29)
        tokens = rng.sample(range(10 ** 7), skip + len(slots))[skip:]
        slots = list(slots)
        rng.shuffle(slots)
        return [daemon_pod(self.sets[g], f"{tag}-{tok:07d}-g{g}",
                           f"node-{i}")
                for (g, i), tok in zip(slots, tokens)]

    def pending(self, count: int, seed: int, tag: str) -> list:
        """The `backlog_pods` daemon pods of the nodes that are not full;
        any other count (warm-up's throw-away pods, which must all bind) as
        count / n pods of every DaemonSet on nodes the seed draws among
        those, so that warm-up's batch has the window's classes."""
        if count == self.cfg["backlog_pods"]:
            return self._stamp([(g, i) for g in range(self.n)
                                for i in self.open], seed, tag)
        per, rest = divmod(count, self.n)
        if rest:
            raise SystemExit(f"shapes daemon_pods: {count} pods do not "
                             f"divide over {self.n} DaemonSets")
        on = random.Random(seed * 1_000_003 + 31).sample(self.open, per)
        return self._stamp([(g, i) for g in range(self.n) for i in on],
                           seed, tag)

    def waiting(self, seed: int, tag: str) -> list:
        """The `waiting_pods` daemon pods named for the full nodes."""
        return self._stamp([(g, i) for g in range(self.n)
                            for i in self.full], seed, tag,
                           skip=self.cfg["backlog_pods"])

    def prebound(self, n_nodes: int, count: int) -> list:
        """One filler a full node, bound there, asking its whole CPU."""
        if not count:
            return []
        f = self.cfg["filler"]
        return [{"apiVersion": "v1", "kind": "Pod",
                 "metadata": {"name": f"filler-{i}", "namespace": "default",
                              "uid": f"default/filler-{i}",
                              "labels": {"app": "filler"}},
                 "spec": {"schedulerName": "default-scheduler",
                          "priority": f["priority"],
                          "nodeName": f"node-{i}",
                          "containers": [{
                              "name": "main", "image": "registry/app:v1",
                              "resources": {"requests": {
                                  "cpu": f["cpu"], "memory": f["memory"]}},
                              "ports": []}]}}
                for i in self.full]

    @staticmethod
    def group_of(pod: dict) -> int:
        return int(pod["metadata"]["name"].rsplit("-g", 1)[1])

    def samples(self) -> list:
        return [daemon_pod(ds, f"shape-{g}", "node-0")
                for g, ds in enumerate(self.sets)]

    def extra_objects(self) -> list:
        return [("daemonsets", ds) for ds in self.sets]
