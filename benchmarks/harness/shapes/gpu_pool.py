"""An accelerator pool in a general cluster: nodes of two shapes and pods of
five, as plain v1 dicts.

Nodes are `objects.make_nodes`' (the configuration's `node_cpu` /
`node_memory` / `node_pods`: the CPU nodes), and node i with
`i % pool.every in pool.offsets` is an ACCELERATOR node instead: the pool's
own cpu, memory and pod count, `pool.count` of the extended resource
`pool.resource` in its allocatable (what a device plugin advertises) and the
pool's taint (what GKE puts on a GPU node pool). The count the rule marks is
verified against `pool.nodes`.

Pods: shape 0 is the PLAIN pod (the configuration's `plain` request, no
toleration); shape g >= 1 asks `sizes[g - 1].gpus` of the resource, `per_gpu`
cpu and memory for each of them, and carries the toleration upstream's
ExtendedResourceToleration admission plugin adds to a pod that asks an
extended resource: key = the resource's name, operator Exists, effect
NoSchedule. `backlog` gives how many pods of every shape wait.

Bound at the start (`prebound`): `bound.plain_per_cpu_node` plain pods on
every CPU node, and one whole-node job (the largest size) on the accelerator
node whose rank in the pool is `bound.whole_node_every`'s multiple. Both
counts are verified against `existing_pods`.

Every seed is the same work: every count, request and node is the
configuration's; the seed names the pods and shuffles the order in which they
were created (one priority, so the order of the queue)."""

from __future__ import annotations

import random

from .. import objects


def pool_indices(cfg: dict) -> list:
    pool = cfg["pool"]
    out = [i for i in range(cfg["nodes"])
           if i % pool["every"] in pool["offsets"]]
    if len(out) != pool["nodes"]:
        raise SystemExit(f"shapes gpu_pool: the rule {pool['every']} / "
                         f"{pool['offsets']} marks {len(out)} of "
                         f"{cfg['nodes']} nodes, the pool states "
                         f"{pool['nodes']}")
    return out


def make_nodes(cfg: dict) -> list:
    nodes, pool = objects.make_nodes(cfg), cfg["pool"]
    for i in pool_indices(cfg):
        nodes[i]["status"]["allocatable"].update({
            "cpu": pool["cpu"], "memory": pool["memory"],
            "pods": str(pool["pods"]), pool["resource"]: str(pool["count"])})
        nodes[i]["metadata"]["labels"]["pool"] = "accelerator"
        nodes[i]["spec"] = {"taints": [dict(pool["taint"])]}
    return nodes


def _quantity(base: str, times: int) -> str:
    """`base` (a quantity ending in m or Ki) times an integer."""
    unit = "m" if base.endswith("m") else "Ki"
    return f"{int(base[:-len(unit)]) * times}{unit}"


class Population:
    def __init__(self, cfg: dict, seed: int, work: int):
        self.cfg, pool = cfg, cfg["pool"]
        self.sizes = [s["gpus"] for s in cfg["sizes"]]
        self.n = 1 + len(self.sizes)
        self.backlog = [cfg["plain"]["pods"]] + [s["pods"]
                                                 for s in cfg["sizes"]]
        self.pool = pool_indices(cfg)
        in_pool = set(self.pool)
        self.cpu_nodes = [i for i in range(cfg["nodes"]) if i not in in_pool]
        b = cfg["bound"]
        self.whole = self.pool[::b["whole_node_every"]]
        existing = b["plain_per_cpu_node"] * len(self.cpu_nodes) \
            + len(self.whole)
        if (sum(self.backlog), existing) != (cfg["backlog_pods"],
                                             cfg["existing_pods"]):
            raise SystemExit(
                f"shapes gpu_pool: the shapes give {sum(self.backlog)} "
                f"waiting and {existing} bound pods; the configuration "
                f"states backlog_pods {cfg['backlog_pods']}, existing_pods "
                f"{cfg['existing_pods']}")
        self._templates = [self._template(g) for g in range(self.n)]

    def priority(self, g: int) -> int:
        return 0

    def _template(self, g: int) -> dict:
        pool = self.cfg["pool"]
        if g == 0:
            requests = {"cpu": self.cfg["plain"]["cpu"],
                        "memory": self.cfg["plain"]["memory"]}
        else:
            k, per = self.sizes[g - 1], self.cfg["per_gpu"]
            requests = {"cpu": _quantity(per["cpu"], k),
                        "memory": _quantity(per["memory"], k),
                        pool["resource"]: str(k)}
        spec = {"schedulerName": "default-scheduler", "priority": 0,
                "containers": [{"name": "main", "image": "registry/app:v1",
                                "resources": {"requests": requests},
                                "ports": []}]}
        if g:
            spec["tolerations"] = [{"key": pool["resource"],
                                    "operator": "Exists",
                                    "effect": "NoSchedule"}]
        return spec

    def pod(self, g: int, name: str, node_name: str = "") -> dict:
        spec = dict(self._templates[g])
        if node_name:
            spec["nodeName"] = node_name
        app = "plain" if g == 0 else f"gpu-{self.sizes[g - 1]}"
        return {"apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": name, "namespace": "default",
                             "uid": f"default/{name}",
                             "labels": {"app": app}},
                "spec": spec}

    def pending(self, count: int, seed: int, tag: str) -> list:
        """The configuration's backlog, every shape at its own count; any
        other count (warm-up's throw-away pods) as count / n of every
        shape. Named from seed and tag, in an order the seed shuffles."""
        if count == self.cfg["backlog_pods"]:
            per = self.backlog
        else:
            each, rest = divmod(count, self.n)
            if rest:
                raise SystemExit(f"shapes gpu_pool: {count} pods do not "
                                 f"divide over {self.n} shapes")
            per = [each] * self.n
        rng = random.Random(seed * 1_000_003 + 29)
        slots = [g for g in range(self.n) for _ in range(per[g])]
        rng.shuffle(slots)
        tokens = rng.sample(range(10 ** 7), len(slots))
        return [self.pod(g, f"{tag}-{tok:07d}-g{g}")
                for g, tok in zip(slots, tokens)]

    def prebound(self, n_nodes: int, count: int) -> list:
        if not count:
            return []
        per = self.cfg["bound"]["plain_per_cpu_node"]
        out = [self.pod(0, f"base-plain-{i}-{j}", f"node-{i}")
               for i in self.cpu_nodes for j in range(per)]
        out += [self.pod(self.n - 1, f"base-job-{i}", f"node-{i}")
                for i in self.whole]
        return out

    @staticmethod
    def group_of(pod: dict) -> int:
        return int(pod["metadata"]["name"].rsplit("-g", 1)[1])

    def samples(self) -> list:
        return [self.pod(g, f"shape-{g}") for g in range(self.n)]

    def extra_objects(self) -> list:
        return []
