"""All-or-nothing ML jobs: every pod belongs to a job (a pod group of the
sig-scheduling coscheduling protocol) and carries the job's name and its
min-available, the job's full size, as the two annotations the protocol reads
from a pod. A shape is a (job size, request tier) pair: `job_sizes` x
`request_tiers`, crossed. Of every shape the configuration states how many
jobs are COMPLETE (every member is at the apiserver: these must all be bound)
and how many INCOMPLETE (`incomplete_members_share` of the members exist and
min-available is still the full size: none may be bound); of every size how
many jobs are OVERSIZED (each member asks `oversized_request`, more than a
node has: none can be bound). No affinity, no spread: what binds is capacity
and the group.

Every seed is the same (shape, completeness, priority) table: job j of a kind
of a shape has priority `job_priorities[j % 3]`. The seed names the jobs and
decides the order in which they were created, a job's members together, as a
Job controller makes them."""

from __future__ import annotations

import random

from .. import objects

make_nodes = objects.make_nodes

GROUP = "pod-group.scheduling.sigs.k8s.io/name"
MIN_AVAILABLE = "pod-group.scheduling.sigs.k8s.io/min-available"


def job_table(cfg: dict) -> dict:
    """{"complete" | "incomplete" | "oversized": [(shape, members created,
    min-available, priority, (cpu, memory)), ...]} from the configuration
    alone, and the two pod counts it states, verified."""
    sizes, tiers = cfg["job_sizes"], cfg["request_tiers"]
    prios = cfg["job_priorities"]
    share = cfg["incomplete_members_share"]
    out: dict = {"complete": [], "incomplete": [], "oversized": []}
    for s, size in enumerate(sizes):
        for t, tier in enumerate(tiers):
            shape = s * len(tiers) + t
            for j in range(cfg["complete_jobs_per_shape"]):
                out["complete"].append(
                    (shape, size, size, prios[j % len(prios)], tuple(tier)))
            for j in range(cfg["incomplete_jobs_per_shape"]):
                out["incomplete"].append(
                    (shape, int(size * share), size, prios[j % len(prios)],
                     tuple(tier)))
        for j in range(cfg["oversized_jobs_per_size"]):
            out["oversized"].append(
                (s * len(tiers), size, size, prios[j % len(prios)],
                 tuple(cfg["oversized_request"])))
    must = sum(job[1] for job in out["complete"])
    must_not = sum(job[1] for kind in ("incomplete", "oversized")
                   for job in out[kind])
    if (must, must_not) != (cfg["backlog_pods"], cfg["waiting_pods"]):
        raise SystemExit(
            f"shapes gang_jobs: the job counts give {must} pods in complete "
            f"jobs and {must_not} in the others; the configuration states "
            f"backlog_pods {cfg['backlog_pods']} and waiting_pods "
            f"{cfg['waiting_pods']}")
    return out


def _pod(name: str, job: str, shape: int, least: int, priority: int,
         request: tuple) -> dict:
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": name, "namespace": "default",
                         "uid": f"default/{name}",
                         "labels": {"app": job, "shape": f"shape-{shape}"},
                         "annotations": {GROUP: job,
                                         MIN_AVAILABLE: str(least)}},
            "spec": {"schedulerName": "default-scheduler",
                     "priority": priority,
                     "containers": [{
                         "name": "worker", "image": "registry/train:v1",
                         "resources": {"requests": {"cpu": request[0],
                                                    "memory": request[1]}},
                         "ports": []}]}}


def _stamp(jobs: list, seed: int, tag: str, skip: int = 0) -> list:
    """The pods of `jobs`, named from seed and tag, the jobs in an order the
    seed shuffles, a job's members together. `skip` tokens of the seed's
    draw are another call's: the two name no job alike."""
    rng = random.Random(seed * 1_000_003 + 29)
    tokens = rng.sample(range(10 ** 6), skip + len(jobs))[skip:]
    order = list(range(len(jobs)))
    rng.shuffle(order)
    out = []
    for k, tok in zip(order, tokens):
        shape, members, least, priority, request = jobs[k]
        job = f"{tag}-{tok:06d}"
        out += [_pod(f"{job}-w{m}", job, shape, least, priority, request)
                for m in range(members)]
    return out


class Population:
    def __init__(self, cfg: dict, seed: int, work: int):
        self.cfg = cfg
        self.table = job_table(cfg)
        self.n = len(cfg["job_sizes"]) * len(cfg["request_tiers"])

    def pending(self, count: int, seed: int, tag: str) -> list:
        """The complete jobs, `backlog_pods` pods; any other count (the
        throw-away pods of warm-up, which waits for every one to be bound)
        as whole jobs of the smallest size over the tiers in turn, the last
        job the remainder."""
        if count == self.cfg["backlog_pods"]:
            return _stamp(self.table["complete"], seed, tag)
        size, tiers = min(self.cfg["job_sizes"]), self.cfg["request_tiers"]
        prios = self.cfg["job_priorities"]
        jobs = []
        while count > 0:
            k = len(jobs)
            jobs.append((k % len(tiers), min(size, count), min(size, count),
                         prios[k % len(prios)], tuple(tiers[k % len(tiers)])))
            count -= size
        return _stamp(jobs, seed, tag)

    def waiting(self, seed: int, tag: str) -> list:
        """The `waiting_pods` pods of the incomplete and the oversized jobs:
        at the apiserver with the others, and never to be bound."""
        return _stamp(self.table["incomplete"] + self.table["oversized"],
                      seed, tag, skip=len(self.table["complete"]))

    def prebound(self, n_nodes: int, count: int) -> list:
        if count:
            raise SystemExit("shapes gang_jobs: no placement rule for a "
                             "pre-bound population: kinds that pre-bind "
                             "none only")
        return []

    @staticmethod
    def group_of(pod: dict) -> int:
        return int(pod["metadata"]["labels"]["shape"][len("shape-"):])

    def samples(self) -> list:
        tiers = self.cfg["request_tiers"]
        return [_pod(f"shape-{g}", f"shape-{g}", g, 1, 0,
                     tuple(tiers[g % len(tiers)])) for g in range(self.n)]

    def extra_objects(self) -> list:
        return []
