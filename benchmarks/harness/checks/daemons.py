"""DaemonSet pods: what ScheduleDaemonSetPods leaves to the scheduler, written
straight over v1 dicts (it imports nothing of the program and takes nothing
the program has made). A DAEMON POD here is a pod whose controller owner is a
DaemonSet; its PIN is the node every term of its required node affinity names
by `matchFields metadata.name In [<one node>]` (a daemon pod without one, or
whose terms disagree, counts under `pinned_elsewhere` wherever it is). From
each pod's own JSON: its pin, its terms' matchExpressions, its tolerations,
its requests. Three counts over a listing, each with the limit 0:

  pinned_elsewhere     a daemon pod bound on another node than its pin, and
                       a second pod of one DaemonSet on one node
  daemon_on_full_node  a daemon pod bound where it cannot be: on a node whose
                       NoSchedule / NoExecute taints it does not tolerate
                       (a cordon among them), whose labels its terms'
                       expressions refuse, or which the pods bound there
                       overfill (cpu, memory, pod count) with it among them;
                       and ANY other pod bound on a cordoned node without
                       tolerating the cordon
  daemon_missing       a daemon pod left pending though its node takes it:
                       the node exists, its taints are tolerated, its labels
                       match, and the pod fits beside everything bound there

`final_state` gives the first (the harness's name for this check);
`counts()` gives all three, and the wiring `local_daemons` publishes the
other two by name. The pods named for a full node must therefore stay
pending (bound, they overfill it) and every other one must be bound (pending,
its node would take it): both ways round, on every seed."""

from __future__ import annotations

from .. import reference

NAMES = ("pinned_elsewhere", None)

COUNTS = ("pinned_elsewhere", "daemon_on_full_node", "daemon_missing")
UNSCHEDULABLE = "node.kubernetes.io/unschedulable"


def owner_daemonset(pod: dict) -> str:
    """The DaemonSet that controls the pod, "" for none."""
    for ref in pod["metadata"].get("ownerReferences") or ():
        if ref.get("controller") and ref.get("kind") == "DaemonSet":
            return ref["name"]
    return ""


def _required_terms(pod: dict) -> list:
    aff = ((pod["spec"].get("affinity") or {}).get("nodeAffinity") or {})
    req = aff.get("requiredDuringSchedulingIgnoredDuringExecution") or {}
    return req.get("nodeSelectorTerms") or []


def pin_of(pod: dict) -> str:
    """The one node every required term names, "" where there is none."""
    names = set()
    terms = _required_terms(pod)
    for t in terms:
        fields = [f for f in t.get("matchFields") or ()
                  if f.get("key") == "metadata.name"
                  and f.get("operator") == "In"]
        if len(fields) != 1 or len(fields[0].get("values") or ()) != 1:
            return ""
        names.add(fields[0]["values"][0])
    return names.pop() if len(names) == 1 else ""


def labels_match(pod: dict, node: dict) -> bool:
    """Some required term's matchExpressions hold of the node's labels (the
    fields are the pin's business); no term at all matches."""
    terms = _required_terms(pod)
    labels = node["metadata"].get("labels") or {}
    return not terms or any(reference.matches(
        tuple((e["key"], e["operator"], tuple(e.get("values") or ()))
              for e in t.get("matchExpressions") or ()), labels)
        for t in terms)


def tolerates(pod: dict, node: dict) -> bool:
    """PodToleratesNodeTaints over NoSchedule and NoExecute taints; a node
    with `spec.unschedulable` counts as carrying the cordon's taint
    (CheckNodeUnschedulable)."""
    spec = node.get("spec") or {}
    taints = [t for t in spec.get("taints") or ()
              if t.get("effect") in ("NoSchedule", "NoExecute")]
    if spec.get("unschedulable") and not any(
            t["key"] == UNSCHEDULABLE for t in taints):
        taints.append({"key": UNSCHEDULABLE, "effect": "NoSchedule"})
    tols = pod["spec"].get("tolerations") or ()

    def tolerated(taint: dict) -> bool:
        for tol in tols:
            if tol.get("effect") and tol["effect"] != taint["effect"]:
                continue
            if tol.get("key") and tol["key"] != taint["key"]:
                continue
            if tol.get("operator", "Equal") == "Exists" \
                    or tol.get("value", "") == taint.get("value", ""):
                return True
        return False

    return all(tolerated(t) for t in taints)


def counts(nodes: list, pods: list, ctx: dict) -> dict:
    """The three lists of violations over one listing."""
    out = {name: [] for name in COUNTS}
    by_name = {n["metadata"]["name"]: n for n in nodes}
    alloc, used, seen = {}, {}, set()
    for name, n in by_name.items():
        a = n["status"]["allocatable"]
        alloc[name] = (reference.milli_cpu(a["cpu"]),
                       reference.kib(a["memory"]), int(a["pods"]))
        used[name] = [0, 0, 0]
    bound = [p for p in pods if (p.get("spec") or {}).get("nodeName")]
    for p in bound:
        u = used.get(p["spec"]["nodeName"])
        if u is not None:
            cpu, mem = reference.requests(p)
            u[0] += cpu
            u[1] += mem
            u[2] += 1
    over = {name for name, u in used.items()
            if any(x > cap for x, cap in zip(u, alloc[name]))}
    for p in bound:
        name, at = p["metadata"]["name"], p["spec"]["nodeName"]
        node, ds = by_name.get(at), owner_daemonset(p)
        if node is None:
            continue
        if not tolerates(p, node):
            out["daemon_on_full_node"].append(
                f"{name} on {at}: its taints are not tolerated")
        if not ds:
            continue
        if pin_of(p) != at:
            out["pinned_elsewhere"].append(
                f"{name} of {ds} on {at}, pinned to {pin_of(p) or 'nothing'}")
        if (ds, at) in seen:
            out["pinned_elsewhere"].append(
                f"{name}: a second pod of {ds} on {at}")
        seen.add((ds, at))
        if at in over:
            out["daemon_on_full_node"].append(
                f"{name} on {at}: the node is over its allocatable")
        elif not labels_match(p, node):
            out["daemon_on_full_node"].append(
                f"{name} on {at}: its node affinity refuses the labels")
    for p in pods:
        if (p.get("spec") or {}).get("nodeName") or not owner_daemonset(p):
            continue
        node = by_name.get(pin_of(p))
        if node is None or not tolerates(p, node) \
                or not labels_match(p, node):
            continue
        at = node["metadata"]["name"]
        cpu, mem = reference.requests(p)
        if all(x + d <= cap for x, d, cap in zip(
                used[at], (cpu, mem, 1), alloc[at])):
            out["daemon_missing"].append(
                f"{p['metadata']['name']} is pending; {at} takes it")
    return out


def final_state(nodes: list, pods: list, ctx: dict) -> list:
    return counts(nodes, pods, ctx)["pinned_elsewhere"]
