"""`pool_backlog`: `backlog` on a cluster that is not empty when the
scheduler starts: the configuration's `existing_pods` are bound by its
shape's rule (`prebound`) before anything else, and the measured scheduler
lists them beside the backlog. Every pod of the backlog must bind; nothing
is sent inside the window and nothing is deleted."""

from __future__ import annotations

from . import backlog


class Kind(backlog.Kind):
    def __init__(self, tr: dict, cfg: dict, seconds: float):
        super().__init__(tr, cfg, seconds)
        self.prebound = cfg["existing_pods"]
