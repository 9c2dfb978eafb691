"""Preemption's guarantees, for a configuration whose every node is filled
with `existing_pods / nodes` pods of `priority_shapes[0]` (the filler) before
pods of `priority_shapes[1]` (the preemptor) arrive
(shapes/priority_fill.py). A plain reference: nothing of the program.

final_state (`victims_evicted_for_nothing`), over what the apiserver lists
when the run is over, node by node: a node that holds fewer fillers than the
rule gave it lost pods to eviction, and then
  - a preemptor is bound on it (else they were evicted for nothing),
  - every preemptor bound on it is of higher priority than the filler, and
  - every preemptor bound on it carries a published nomination
    (`status.nominatedNodeName`): the scheduler said where it was sending
    the pod before it deleted anything for it. A pod that took room evicted
    for no one carries none.
In set-up the same function holds the pre-bound population to the rule.

replay (`victims_beyond_minimum`), over the client's watch history: the
deletions and Bindings in the order they arrived, applied to the pre-bound
population (reference.World). At the end, on every node that lost pods, each
evicted pod is offered its place back, highest priority first (upstream's
reprieve, selectVictimsOnNode): one that fits beside everything bound there
was evicted beyond the minimum. Also: a pod of the run's own backlog deleted,
and an evicted pod not of lower priority than the preemptors bound in its
place. A deletion of a pod that is neither of the population nor of the run
is a warm-up pod leaving: what warm-up evicted the kind re-created and held
to the rule before the window (kinds/preempt_backlog.py), so the world is
made whole there.

Beside the check, the plain SEQUENTIAL reference of upstream's pass for pods
that meet only through a node's resources (`sequential_pass`): one preemptor
at a time in queue order, each what-if over the cluster as the earlier ones
left it (their victims gone, themselves nominated on their nodes);
`select_victims` is selectVictimsOnNode (remove every pod of lower priority,
fit, reprieve the more important first) and `pick_one_node`
pickOneNodeForPreemption's keys (no PDBs here, so four of the five: lowest
highest-victim priority, smallest priority sum, fewest victims, latest start
of the highest-priority victims; a tie falls to the node listed first, which
is the program's tie-break too: the lower index in its snapshot's node
order). The tier-1 tests hold the program's one-pass hand-out to it on
seeded random clusters (tests/test_preempt_handout.py)."""

from __future__ import annotations

from .. import reference

NAMES = ("victims_evicted_for_nothing", "victims_beyond_minimum")


# ---- upstream's pass, one preemptor at a time (plain Python) ------------- #
# a node: {"name", "cpu", "memory", "pods"} (allocatable); a pod: {"name",
# "cpu", "memory", "priority", "start"} and, once on a node, "node"


def _fits(node: dict, pods: list, ask: dict) -> bool:
    return (sum(p["cpu"] for p in pods) + ask["cpu"] <= node["cpu"]
            and sum(p["memory"] for p in pods) + ask["memory"]
            <= node["memory"] and len(pods) + 1 <= node["pods"])


def select_victims(node: dict, on_node: list, preemptor: dict):
    """selectVictimsOnNode: None where the preemptor does not fit even with
    every pod of lower priority gone, else the victims: those that cannot be
    reprieved, offered their place back by priority (higher first), then by
    start (earlier first)."""
    lower = [p for p in on_node if p["priority"] < preemptor["priority"]]
    kept = [p for p in on_node if p["priority"] >= preemptor["priority"]]
    if not _fits(node, kept, preemptor):
        return None
    victims = []
    for p in sorted(lower, key=lambda p: (-p["priority"], p["start"])):
        if _fits(node, kept + [p], preemptor):
            kept.append(p)
        else:
            victims.append(p)
    return victims


def node_key(victims: list) -> tuple:
    """pickOneNodeForPreemption's keys over one node's victims, smaller is
    better: highest victim priority, priority sum, count, and the start of
    the earliest of the highest-priority victims, later preferred."""
    if not victims:
        return (-(1 << 31), 0, 0, -(1 << 31))
    top = max(p["priority"] for p in victims)
    return (top, sum(p["priority"] for p in victims), len(victims),
            -min(p["start"] for p in victims if p["priority"] == top))


def pick_one_node(candidates: dict, listed: list):
    """The candidate ({node name: victims}) with the least keys; a tie goes
    to the node `listed` first."""
    return min(candidates, key=lambda n: (node_key(candidates[n]),
                                          listed.index(n)), default=None)


def sequential_pass(nodes: list, bound: list, preemptors: list) -> list:
    """[(preemptor name, node name or None, victims)] in queue order (higher
    priority first, then as given). A preemptor that was sent to a node
    stays there, nominated: later ones of no higher priority find it among
    the node's pods (addNominatedPods), never among their victims."""
    by_node = {n["name"]: [p for p in bound if p["node"] == n["name"]]
               for n in nodes}
    listed = [n["name"] for n in nodes]
    out = []
    for pre in sorted(preemptors, key=lambda p: -p["priority"]):
        candidates = {}
        for n in nodes:
            victims = select_victims(n, by_node[n["name"]], pre)
            if victims is not None:
                candidates[n["name"]] = victims
        best = pick_one_node(candidates, listed)
        out.append((pre["name"], best, candidates.get(best, [])))
        if best is not None:
            gone = {p["name"] for p in candidates[best]}
            by_node[best] = [p for p in by_node[best]
                             if p["name"] not in gone] + [pre]
    return out


def _priority(pod: dict) -> int:
    return int(pod["spec"].get("priority", 0) or 0)


def final_state(nodes: list, pods: list, ctx: dict) -> list:
    cfg = ctx["cfg"]
    filler, preemptor = cfg["priority_shapes"]
    per_node = cfg["existing_pods"] // cfg["nodes"]
    fillers: dict = {}     # node -> fillers bound there
    preemptors: dict = {}  # node -> preemptors bound there
    for p in pods:
        node = (p.get("spec") or {}).get("nodeName")
        if not node:
            continue
        if _priority(p) >= preemptor["priority"]:
            preemptors.setdefault(node, []).append(p)
        else:
            fillers[node] = fillers.get(node, 0) + 1
    bad = []
    for n in nodes:
        name = n["metadata"]["name"]
        lost = per_node - fillers.get(name, 0)
        if lost <= 0:
            continue
        here = preemptors.get(name, [])
        if not here:
            bad.append(f"node {name}: {lost} of its {per_node} pods evicted "
                       "and no preemptor bound there")
        for p in here:
            who = p["metadata"]["name"]
            if _priority(p) <= filler["priority"]:
                bad.append(f"node {name}: {who} (priority {_priority(p)}) "
                           f"took the place of {lost} pods of priority "
                           f"{filler['priority']}")
            if not (p.get("status") or {}).get("nominatedNodeName"):
                bad.append(f"node {name}: {who} is bound where {lost} pods "
                           "were evicted and carries no "
                           "status.nominatedNodeName")
    return bad


def replay(nodes: list, prebound: list, history: list, by_name: dict,
           shapes: list, ctx: dict) -> tuple:
    world = reference.World(nodes, shapes)
    population = {p["metadata"]["name"]: p for p in prebound}
    for p in prebound:
        world.add(p, p["spec"]["nodeName"])
    evicted: dict = {}   # node -> pods of the population deleted from it
    landed: dict = {}    # node -> pods of the run bound on it
    checked, bad = 0, []
    for what, name, node in history:
        if what == "bound":
            pod = by_name.get(name)
            if pod is not None and name not in world.placed:
                checked += 1
                world.add(pod, node)
                landed.setdefault(node, []).append(pod)
            continue
        if name in population:
            was = world.placed.get(name)
            if was is not None:
                evicted.setdefault(was[0], []).append(population[name])
                world.remove(name)
        elif name in by_name:
            bad.append(f"{name}: a pod of the run's own backlog was deleted")
            world.remove(name)
        else:
            # a throw-away pod leaves: the population was made whole
            for at, pods in evicted.items():
                for p in pods:
                    world.add(p, at)
            evicted.clear()
    for node, gone in evicted.items():
        top = max(_priority(p) for p in gone)
        for pod in landed.get(node, ()):
            if _priority(pod) <= top:
                bad.append(f"node {node}: a pod of priority {top} evicted "
                           f"for {pod['metadata']['name']} of priority "
                           f"{_priority(pod)}")
        for p in sorted(gone, key=lambda p: (-_priority(p),
                                             p["metadata"]["name"])):
            if not world.why_not(p, node):
                bad.append(f"node {node}: {p['metadata']['name']} was "
                           "evicted and still fits beside everything bound "
                           "there")
                world.add(p, node)
    return checked, bad
