"""The preemption configuration's controls, for `run_cell(sabotage=)` and the
control runs on the chip (`chip_control_preempt.py`): each breaks one
guarantee `preempt-5k` adds, and the run must come out `correct: false` by
the count named. Both sit where the pass meets the API (the evictor), know
the population's rule (shapes/priority_fill.py: `base-<k*n + j>` on
`node-<n>`), and leave the what-if alone."""

from __future__ import annotations

import zlib


def _per_node(cluster) -> int:
    return cluster.cfg["existing_pods"] // cluster.cfg["nodes"]


def skip_reprieve(cluster, server) -> None:
    """Every pod of lower priority on a preemptor's node is evicted, not the
    three the reprieve leaves: with a victim go all its node's pods. Caught by
    `victims_beyond_minimum`, one count a node (the fourth pod fits back)."""
    evictor, per = server.scheduler.preemptor.evictor, _per_node(cluster)
    real = evictor.evict

    def evict(scheduler, victim_key):
        done = real(scheduler, victim_key)
        i = int(victim_key.rsplit("-", 1)[1])
        for j in range(i - i % per, i - i % per + per):
            real(scheduler, f"default/base-{j}")
        return done

    evictor.evict = evict


def evict_unhanded_nodes(cluster, server) -> None:
    """For every other preemptor the pass deletes the victims it chose and
    then sends the pod nowhere: no nomination, published or kept. The pod
    later takes room that was evicted for no one, and carries no
    `status.nominatedNodeName`. Caught by `victims_evicted_for_nothing`."""
    evictor, per = server.scheduler.preemptor.evictor, _per_node(cluster)
    real = evictor.nominate

    def nominate(scheduler, pod, node_name):
        if zlib.crc32(pod.name.encode()) % 2:
            return real(scheduler, pod, node_name)
        n = int(node_name.rsplit("-", 1)[1])
        for j in range(per - 1):
            evictor.evict(scheduler, f"default/base-{n * per + j}")
        return False

    evictor.nominate = nominate


CONTROLS = {"skip_reprieve": skip_reprieve,
            "evict_unhanded_nodes": evict_unhanded_nodes}
