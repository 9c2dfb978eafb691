"""The extender's answers, held to the plain reference (`reference.py`; this
module imports nothing of the program). Of every 10th pod the stand-in keeps
the `filter` answer it got (`kinds/extender_loop.py` `KEPT`). The loop is
serial and every `bind` is awaited, so the cluster at that call is the
pre-bound population plus every Binding the client's watch saw BEFORE the
pod's own: the watch history is replayed up to there and the reference's
predicates are run over EVERY node the `filter` was asked about (the
reference keeps its domain counts incrementally, so a node costs the same
whatever the cluster's size: 5,000 nodes an answer, no sample).

  filter_answers_wrong          one for each node the extender passed and the
                                reference refuses, or the reverse, and one
                                for an answer whose `FailedNodes` are not
                                exactly the nodes it did not pass
  prioritize_answers_malformed  one for each `prioritize` answer that left a
                                candidate unscored, scored one twice, scored
                                a host it was not asked about, or gave a
                                score that is not a whole number from 0 to 10
                                (found by the stand-in as it chooses)

A pod that ran twice (an errored call, retried) keeps its LAST `filter`.
"""

from __future__ import annotations

from .. import reference
from ..kinds import extender_loop

NAMES = ("prioritize_answers_malformed", "filter_answers_wrong")


def final_state(nodes: list, pods: list, ctx: dict) -> list:
    return list(extender_loop.KEPT.malformed)


def replay(nodes: list, prebound: list, history: list, by_name: dict,
           shapes: list, ctx: dict) -> tuple:
    kept = extender_loop.KEPT.filters
    world = reference.World(nodes, shapes)
    for p in prebound:
        world.add(p, p["spec"]["nodeName"])
    looked, bad = 0, []
    for what, name, node in history:
        if what == "deleted":
            world.remove(name)
            continue
        pod = by_name.get(name)
        if pod is None or name in world.placed:
            continue
        if name in kept:
            looked += 1
            bad += compare(world, pod, *kept[name])
        world.add(pod, node)
    return looked, bad


def compare(world, pod: dict, asked: list, passed: list,
            failed: dict) -> list:
    """One kept `filter` answer against the reference in `world`."""
    name, ok = pod["metadata"]["name"], set(passed)
    bad = []
    for node in asked:
        why = world.why_not(pod, node)
        if why and node in ok:
            bad.append(f"filter {name}: passed {node}, the reference "
                       f"refuses it: {why}")
        elif not why and node not in ok:
            bad.append(f"filter {name}: refused {node} "
                       f"({failed.get(node)!r}), the reference takes it")
    if set(failed) != set(asked) - ok or not ok <= set(asked):
        bad.append(f"filter {name}: {len(failed)} FailedNodes for "
                   f"{len(asked) - len(ok)} nodes not passed")
    return bad
