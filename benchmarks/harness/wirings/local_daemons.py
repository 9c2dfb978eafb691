"""`local_daemons`: the `local` wiring, with the plain reference's DaemonSet
counts published by name. `counters()["zero"]` gains `daemon_on_full_node`
and `daemon_missing` (checks/daemons.py `counts()` over the apiserver's
listing when the run is over; `pinned_elsewhere`, the third, is the check's
own count), and `counters()["info"]` how many daemon pods are bound and how
many pending.

A tree whose program has no pin (`PodArrays` without the field: every tree
before ISSUE 49) cannot run this configuration inside `run_seconds`: there a
pod's class is keyed by the node its affinity names, the 20,000 daemon pods
are 20,000 classes (`Dims.SC` 32,768 where warm-up's 32 pods gave 64), and the
window compiles a program no warm-up made (measured on the chip, PR 49: ~70 s
of compile, the first Binding 57 s after the window opened, no device
operation inside the 40 s). `Cluster` says so and ends the run at once, before
anything is built, so that such a tree fails this cell cleanly."""

from __future__ import annotations

from ..checks import daemons
from . import local


class Cluster(local.Cluster):
    def __init__(self, cfg: dict):
        from kubernetes_tpu.state.arrays import PodArrays

        if "pin" not in PodArrays._fields:
            raise SystemExit(
                "wiring local_daemons: this tree's program has no pin "
                "(state/arrays.py PodArrays): each of the configuration's "
                "daemon pods would be a scheduling class of its own and the "
                "window would compile a program no warm-up made. The cell "
                "needs ISSUE 49's pin; this tree cannot run it.")
        super().__init__(cfg)

    def counters(self, server) -> dict:
        out = super().counters(server)
        if server.pod_informer is None:
            return out   # not started: set-up reads the relists alone
        nodes = self.client.nodes.list()["items"]
        pods = self.client.pods.list("default")["items"]
        found = daemons.counts(nodes, pods, {"cfg": self.cfg})
        for name in ("daemon_on_full_node", "daemon_missing"):
            out["zero"][name] = len(found[name])
            out["info"][name + "_first"] = found[name][:3]
        owned = [p for p in pods if daemons.owner_daemonset(p)]
        bound = sum(1 for p in owned if (p.get("spec") or {}).get("nodeName"))
        out["info"]["daemon_pods_bound"] = bound
        out["info"]["daemon_pods_pending"] = len(owned) - bound
        return out
