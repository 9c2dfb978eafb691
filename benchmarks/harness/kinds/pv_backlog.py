"""`pv_backlog`: `backlog` on a cluster that already runs pods, whose waiting
pods each name a PersistentVolumeClaim. The configuration's `existing_pods`
are bound before the scheduler starts (`prebound`), by the shape's rule; the
backlog's PVs and bound claims are at the apiserver from set-up on
(`shapes.extra_objects`), so when the window opens at `server.start()` the
measured scheduler's informers list the nodes, their CSINodes, both pod
populations, the PVs and the claims inside it. Nothing is sent inside the
window and nothing is deleted (`check_spread` stays true).

Warm-up's throw-away pods had PVs and claims of their own; `prepare` deletes
those once the warm-up scheduler is stopped, so the window's lists hold the
backlog's alone."""

from __future__ import annotations

from ..probes import log
from . import backlog


class Kind(backlog.Kind):
    def __init__(self, tr: dict, cfg: dict, seconds: float):
        super().__init__(tr, cfg, seconds)
        self.prebound = cfg["existing_pods"]
        warm = cfg["warmup"]
        if (tr["warmup_rounds"], tr["warmup_pods_per_group"]) != (
                warm["rounds"], warm["pods"]):
            raise SystemExit(
                "kind pv_backlog: the mix warms up with "
                f"{tr['warmup_rounds']} x {tr['warmup_pods_per_group']} "
                f"pods, the configuration made volumes for {warm}")

    def prepare(self, cluster, server, watch, shapes, seed: int) -> tuple:
        server.stop()   # the warm-up scheduler must not see what follows
        rule, client = self.cfg["volume_rule"], cluster.client
        names = shapes.warmup_names()
        for name in names:
            client.persistentvolumeclaims.delete(
                rule["claim_prefix"] + name, "default")
            client.persistentvolumes.delete(rule["pv_prefix"] + name, "")
        log(f"set-up: warm-up's {len(names)} PVs and claims deleted")
        return super().prepare(cluster, server, watch, shapes, seed)
