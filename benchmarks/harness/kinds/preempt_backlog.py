"""`preempt_backlog`: `backlog` on a cluster that is FULL. The
configuration's `existing_pods` are bound before the scheduler starts
(`prebound`), the backlog waits at the apiserver, and a pod of it can land
only where the scheduler evicts; pods are deleted inside the window, so a
final skew is no placement's (`check_spread` false).

Warm-up's throw-away pods are preemptors too: to bind they evict, which is how
the what-if's executable gets loaded before the window. What they evicted is
part of the measured population, so `prepare` puts it back: every pod of
`shapes.prebound` that the apiserver no longer lists is created again, bound
by the same rule under the same name, and the listing is then held to the
rule, pod for pod, before the measured scheduler exists. The client's watch
saw those deletions (its history keeps them); it does not see the re-creation
as a Binding, the name being one it has seen on that node. checks/preemption.py
knows: a throw-away pod's own deletion is the line after which the population
was whole."""

from __future__ import annotations

from ..probes import log
from ..traffic import create_all
from . import backlog


class Kind(backlog.Kind):
    check_spread = False

    def __init__(self, tr: dict, cfg: dict, seconds: float):
        super().__init__(tr, cfg, seconds)
        self.prebound = cfg["existing_pods"]

    def prepare(self, cluster, server, watch, shapes, seed: int) -> tuple:
        server.stop()   # the warm-up scheduler must not act on what follows
        pods = cluster.client.pods
        population = shapes.prebound(self.cfg["nodes"], self.prebound)
        listed = {p["metadata"]["name"] for p in pods.list("default")["items"]}
        missing = [p for p in population
                   if p["metadata"]["name"] not in listed]
        create_all(pods, missing)
        want = {p["metadata"]["name"]: p["spec"]["nodeName"]
                for p in population}
        have = {p["metadata"]["name"]: (p.get("spec") or {}).get("nodeName")
                for p in pods.list("default")["items"]}
        if have != want:
            odd = sorted(set(have.items()) ^ set(want.items()))[:3]
            raise SystemExit("set-up: after warm-up the bound population is "
                             f"not the rule's ({len(have)} pods listed, "
                             f"{len(want)} by the rule): {odd}")
        log(f"set-up: warm-up evicted {len(missing)} pods of the population; "
            f"re-created, {len(have)} bound as the rule has them")
        return super().prepare(cluster, server, watch, shapes, seed)
