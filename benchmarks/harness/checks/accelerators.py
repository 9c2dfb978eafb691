"""Extended resources, taints and the documented bin-packing score, written
straight over v1 dicts (it imports nothing of the program and takes nothing
the program has made).

An EXTENDED resource is a name with a `/` in a container's requests or a
node's allocatable (`nvidia.com/gpu`); its quantities are whole numbers. From
each pod's own JSON: what it asks of every resource, its tolerations. Three
counts, each with the limit 0:

  nodes_over_extended_resource   over the final listing: a node whose bound
                       pods ask more of an extended resource than its
                       allocatable has, the resource it lacks altogether
                       among them (`final_state`, the harness's name for
                       this check)
  pods_on_untolerated_taint      over the final listing: a pod bound on a
                       node one of whose NoSchedule / NoExecute taints it
                       does not tolerate (`counts()`; the wiring
                       `local_policy` publishes it by name)
  extended_bindings_refused_at_their_turn   the client's watch history
                       replayed in the order the Bindings landed: a Binding
                       of a pod that asks an extended resource onto a node
                       that, in the world as it stood, had not that much of
                       it left, or whose taints the pod does not tolerate
                       (`replay`)

`placement` holds cpu, memory and the pod count beside them.

And upstream's sequential loop (`sequential`), the plain reference of WHICH
node a scheduler under the configuration's Policy picks: one pod at a time in
queue order, Filter (every resource of the pod fits beside what the node
holds, the pod count among them; taints tolerated), then the Policy's
`RequestedToCapacityRatioPriority` (`rtc_score`, the equations of
priorities/requested_to_capacity_ratio.go v1.17 as the documentation's worked
example gives them) times its weight, the highest score wins, the lowest
node index among equals (upstream draws among equals at random), and the
node's requested resources grow by the pod's before the next pod is looked
at. The Policy's other priorities (taint toleration, node affinity, pod
affinity) read constant over the nodes a pod of these configurations may
use, and are left out. Its result is how many accelerator nodes a packing
scheduler OPENS for the backlog: the wiring publishes it beside what the
system under test opened."""

from __future__ import annotations

import heapq

from .. import reference
from . import daemons

NAMES = ("nodes_over_extended_resource",
         "extended_bindings_refused_at_their_turn")

COUNTS = ("nodes_over_extended_resource", "pods_on_untolerated_taint")

#: priorities/util/non_zero.go: what a pod that asks no cpu / no memory
#: counts for in a resource score (milli-CPU, KiB)
DEFAULT_MILLI_CPU, DEFAULT_MEMORY_KIB = 100, 200 * 1024


def extended(quantities: dict) -> dict:
    return {k: int(v) for k, v in (quantities or {}).items() if "/" in k}


def asks(pod: dict) -> dict:
    """What the pod asks of every extended resource, summed over its
    containers."""
    out: dict = {}
    for c in pod["spec"].get("containers", ()):
        for k, v in extended((c.get("resources") or {}).get("requests")
                             ).items():
            out[k] = out.get(k, 0) + v
    return out


def counts(nodes: list, pods: list, ctx: dict) -> dict:
    """The two lists of violations over one listing."""
    out = {name: [] for name in COUNTS}
    by_name = {n["metadata"]["name"]: n for n in nodes}
    used: dict = {}
    for p in pods:
        at = (p.get("spec") or {}).get("nodeName")
        node = by_name.get(at)
        if node is None:
            continue
        for k, v in asks(p).items():
            used.setdefault(at, {})[k] = used.get(at, {}).get(k, 0) + v
        if not daemons.tolerates(p, node):
            out["pods_on_untolerated_taint"].append(
                f"{p['metadata']['name']} on {at}")
    for at, u in used.items():
        have = extended(by_name[at]["status"]["allocatable"])
        for k, v in u.items():
            if v > have.get(k, 0):
                out["nodes_over_extended_resource"].append(
                    f"{at}: its pods ask {v} of {k}, it has "
                    f"{have.get(k, 0)}")
    return out


def final_state(nodes: list, pods: list, ctx: dict) -> list:
    return counts(nodes, pods, ctx)["nodes_over_extended_resource"]


def replay(nodes: list, prebound: list, history: list, by_name: dict,
           shapes: list, ctx: dict) -> tuple:
    node_of = {n["metadata"]["name"]: n for n in nodes}
    have = {name: extended(n["status"]["allocatable"])
            for name, n in node_of.items()}
    used: dict = {name: {} for name in node_of}
    where: dict = {}

    def place(pod: dict, at: str) -> None:
        for k, v in asks(pod).items():
            used[at][k] = used[at].get(k, 0) + v

    for p in prebound:
        place(p, p["spec"]["nodeName"])
        where[p["metadata"]["name"]] = (p, p["spec"]["nodeName"])
    looked, refused = 0, []
    for what, name, at in history:
        if what == "deleted":
            pod, node = where.pop(name, (None, None))
            if pod is not None:
                for k, v in asks(pod).items():
                    used[node][k] -= v
            continue
        pod = by_name.get(name)
        if pod is None or at not in node_of or name in where:
            continue
        want = asks(pod)
        if want:
            looked += 1
            short = [k for k, v in want.items()
                     if used[at].get(k, 0) + v > have[at].get(k, 0)]
            if short:
                refused.append(f"{name} on {at}: no room for its "
                               f"{short[0]}")
            elif not daemons.tolerates(pod, node_of[at]):
                refused.append(f"{name} on {at}: its taints are not "
                               "tolerated")
        place(pod, at)
        where[name] = (pod, at)
    return looked, refused


# --------------------------------------------------------------------------- #
# the score and the sequential loop
# --------------------------------------------------------------------------- #


def rtc_arguments(policy: dict) -> tuple:
    """(priority weight, shape as (utilization, score 0..100) pairs,
    resources as (name, weight) pairs) of the Policy's
    RequestedToCapacityRatioPriority; None where it has none."""
    for pr in policy.get("priorities") or ():
        arg = (pr.get("argument") or {}).get(
            "requestedToCapacityRatioArguments")
        if arg:
            shape = [(int(p["utilization"]), int(p["score"]) * 10)
                     for p in arg["shape"]]
            res = [(r["name"], int(r.get("weight", 1)))
                   for r in arg.get("resources")
                   or ({"name": "cpu"}, {"name": "memory"})]
            return int(pr.get("weight", 1)), shape, res
    return None


def broken_linear(shape: list, p: int) -> int:
    for i, (x, y) in enumerate(shape):
        if p <= x:
            if i == 0:
                return y
            x0, y0 = shape[i - 1]
            num = (y - y0) * (p - x0)
            q = abs(num) // (x - x0)      # Go's integer division truncates
            return y0 + (q if num >= 0 else -q)
    return shape[-1][1]


def rtc_score(requested: dict, allocatable: dict, shape: list,
              resources: list) -> int:
    """requested_to_capacity_ratio.go: `requested` is what the node's pods
    and the incoming pod ask together, by resource name (cpu in milli-CPU,
    memory in KiB). 0..100."""
    num = den = 0
    for name, weight in resources:
        cap, req = allocatable.get(name, 0), requested.get(name, 0)
        util = 100 if cap == 0 or req > cap \
            else 100 - (cap - req) * 100 // cap
        s = broken_linear(shape, util)
        if s > 0:
            num += s * weight
            den += weight
    return 0 if den == 0 else (2 * num + den) // (2 * den)   # math.Round


def quantities(pod: dict, nonzero: bool) -> dict:
    """What the pod asks, by resource name; with `nonzero` a pod that asks
    no cpu / no memory counts the defaults, as a resource SCORE reads it."""
    cpu, mem = reference.requests(pod)
    if nonzero:
        cpu, mem = cpu or DEFAULT_MILLI_CPU, mem or DEFAULT_MEMORY_KIB
    return {"cpu": cpu, "memory": mem, **asks(pod)}


def node_quantities(node: dict) -> dict:
    a = node["status"]["allocatable"]
    return {"cpu": reference.milli_cpu(a["cpu"]),
            "memory": reference.kib(a["memory"]), "pods": int(a["pods"]),
            **extended(a)}


def sequential(nodes: list, bound: list, queue: list, policy: dict) -> dict:
    """Upstream's loop over `queue` (pods in the order the scheduler pops
    them) on `nodes` holding `bound`. Returns {pod name: node name or None}.

    Nodes that are alike (one allocatable, one set of taints, the same
    requested resources) score alike for a pod, so they are kept in buckets
    by that state, each a heap of node indices: a pod is scored once a
    bucket, and lands on the lowest index of the best bucket."""
    weight, shape, resources = rtc_arguments(policy)
    alloc = [node_quantities(n) for n in nodes]
    index = {n["metadata"]["name"]: i for i, n in enumerate(nodes)}
    fit_used = [dict.fromkeys(a, 0) for a in alloc]     # Filter's account
    score_used = [dict.fromkeys(a, 0) for a in alloc]   # the score's
    kind = {}   # (allocatable, taints) -> small int, the node's shape
    shape_of = [kind.setdefault(
        (tuple(sorted(a.items())),
         repr((n.get("spec") or {}).get("taints"))), len(kind))
        for n, a in zip(nodes, alloc)]

    def add(i: int, pod: dict) -> None:
        for k, v in quantities(pod, False).items():
            fit_used[i][k] = fit_used[i].get(k, 0) + v
        fit_used[i]["pods"] += 1
        for k, v in quantities(pod, True).items():
            score_used[i][k] = score_used[i].get(k, 0) + v

    for p in bound:
        add(index[p["spec"]["nodeName"]], p)

    def state(i: int) -> tuple:
        return (shape_of[i], tuple(sorted(fit_used[i].items())),
                tuple(sorted(score_used[i].items())))

    buckets: dict = {}
    for i in range(len(nodes)):
        buckets.setdefault(state(i), []).append(i)   # ascending: a heap
    tolerated: dict = {}   # (the pod's tolerations, node shape) -> bool
    out = {}
    for pod in queue:
        want, scored = quantities(pod, False), quantities(pod, True)
        tols = repr(pod["spec"].get("tolerations"))
        best, best_key = None, None
        for key, members in buckets.items():
            i = members[0]
            ok = tolerated.get((tols, shape_of[i]))
            if ok is None:
                ok = tolerated[(tols, shape_of[i])] = daemons.tolerates(
                    pod, nodes[i])
            if not ok or fit_used[i]["pods"] + 1 > alloc[i]["pods"] or any(
                    fit_used[i].get(k, 0) + v > alloc[i].get(k, 0)
                    for k, v in want.items()):
                continue
            total = {k: score_used[i].get(k, 0) + scored.get(k, 0)
                     for k, _w in resources}
            s = weight * rtc_score(total, alloc[i], shape, resources)
            if best is None or (s, -i) > best:
                best, best_key = (s, -i), key
        name = pod["metadata"]["name"]
        if best is None:
            out[name] = None
            continue
        i = heapq.heappop(buckets[best_key])
        if not buckets[best_key]:
            del buckets[best_key]
        add(i, pod)
        heapq.heappush(buckets.setdefault(state(i), []), i)
        out[name] = nodes[i]["metadata"]["name"]
    return out


def opened(nodes: list, placed: dict, resource: str) -> int:
    """Nodes that have `resource` and hold a pod of `placed` ({pod name:
    node name or None})."""
    have = {n["metadata"]["name"] for n in nodes
            if extended(n["status"]["allocatable"]).get(resource)}
    return len({at for at in placed.values() if at in have})
