"""`gang_backlog`: `backlog` for a configuration whose shapes hold pods that
must NOT be bound (shapes/gang_jobs.py `waiting`: the members of jobs that
cannot reach their min-available). Those pods are at the apiserver with the
backlog before the measured scheduler starts (its informers list by name, and
the seed names both alike, so they reach its queue among the others); the
run's work, `attempted`, the window's close and the names `pods_never_bound`
counts are the backlog's alone. The configuration's `gangs` check holds the
others to staying unbound."""

from __future__ import annotations

from ..probes import log
from ..traffic import create_all
from . import backlog


class Kind(backlog.Kind):
    def prepare(self, cluster, server, watch, shapes, seed: int) -> tuple:
        server.stop()   # the warm-up scheduler must not see them
        waiting = shapes.waiting(seed, "job")
        t_load = create_all(cluster.client.pods, waiting)
        log(f"set-up: {len(waiting)} pods that must stay unbound created in "
            f"{t_load:.1f}s")
        return super().prepare(cluster, server, watch, shapes, seed)
