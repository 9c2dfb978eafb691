"""`daemon_backlog`: `gang_backlog` on a cluster whose full nodes hold a
filler each (`prebound`: the configuration's `existing_pods`, by the shape's
rule). The pods that must NOT be bound are shapes/daemon_pods.py `waiting`:
the daemon pods named for the full nodes, at the apiserver with the backlog
before the measured scheduler starts; the run's work, `attempted`, the
window's close and the names `pods_never_bound` counts are the backlog's
alone. The configuration's `daemons` check and its wiring hold the others to
staying pending, each truly refused by its node. Nothing is sent inside the
window and nothing is deleted."""

from __future__ import annotations

from . import gang_backlog


class Kind(gang_backlog.Kind):
    def __init__(self, tr: dict, cfg: dict, seconds: float):
        super().__init__(tr, cfg, seconds)
        self.prebound = cfg["existing_pods"]
