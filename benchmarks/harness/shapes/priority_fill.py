"""Two pod templates that differ in priority and size, upstream
scheduler_perf's `pod-low-priority` and `pod-high-priority`: the
configuration's `priority_shapes` are [the filler, the preemptor], each a
name, its requests and its `priority`. The fillers are the population bound
before the scheduler starts, `existing_pods` of them, replicas
`k * n .. k * n + k - 1` on node n (k = `existing_pods` / `nodes`): every node
holds the same k, which the configuration's `preemption` check verifies in
set-up. The preemptors are everything `pending` makes: the backlog, and
warm-up's throw-away pods.

No labels the scheduler reads, no affinity, no spread: pods meet only through
a node's resources. Every seed is the same work; it names the pending pods and
decides the order in which they were created. The fillers' names are the
rule's and the same under every seed, so that what a warm-up evicted can be
put back under the same name (kinds/preempt_backlog.py)."""

from __future__ import annotations

import random

from .. import objects

make_nodes = objects.make_nodes


def _pod(shape: dict, g: int, name: str, node_name: str = "") -> dict:
    spec = {"schedulerName": "default-scheduler",
            "priority": shape["priority"],
            "terminationGracePeriodSeconds": 0,
            "containers": [{"name": "pause", "image": "k8s.gcr.io/pause:3.2",
                            "resources": {"requests": {
                                "cpu": shape["cpu"],
                                "memory": shape["memory"]}},
                            "ports": []}]}
    if node_name:
        spec["nodeName"] = node_name
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default",
                         "uid": f"default/{name}",
                         "labels": {"template": shape["name"],
                                    "shape": str(g)}},
            "spec": spec}


class Population:
    def __init__(self, cfg: dict, seed: int, work: int):
        self.shapes = cfg["priority_shapes"]
        if len(self.shapes) != 2 or \
                self.shapes[0]["priority"] >= self.shapes[1]["priority"]:
            raise SystemExit("shapes priority_fill: priority_shapes is [the "
                             "filler, the preemptor], the filler of lower "
                             "priority")
        self.n = len(self.shapes)

    def priority(self, g: int) -> int:
        return self.shapes[g]["priority"]

    def pending(self, count: int, seed: int, tag: str) -> list:
        rng = random.Random(seed * 1_000_003 + 29)
        return [_pod(self.shapes[1], 1, f"{tag}-{tok:07d}-g1")
                for tok in rng.sample(range(10 ** 7), count)]

    def prebound(self, n_nodes: int, count: int) -> list:
        if count % n_nodes:
            raise ValueError(f"{count} fillers do not divide over {n_nodes} "
                             "nodes")
        per = count // n_nodes
        return [_pod(self.shapes[0], 0, f"base-{i}", f"node-{i // per}")
                for i in range(count)]

    @staticmethod
    def group_of(pod: dict) -> int:
        return int(pod["metadata"]["labels"]["shape"])

    def samples(self) -> list:
        return [_pod(s, g, f"shape-{g}") for g, s in enumerate(self.shapes)]

    def extra_objects(self) -> list:
        return []
