"""All-or-nothing pod groups (the sig-scheduling coscheduling protocol): of
the pods the apiserver lists when the run is over, those that name a group
(`pod-group.scheduling.sigs.k8s.io/name`, as a label or an annotation) are
counted per group; a group with some member bound and fewer bound than its
`min-available` (the largest any member states) is partly bound. A job whose
members do not all exist can reach its min-available with none of them, so
any one of them bound counts; a member no node can hold is `placement`'s to
see, should it be bound."""

from __future__ import annotations

NAMES = ("gangs_partly_bound", None)

GROUP = "pod-group.scheduling.sigs.k8s.io/name"
MIN_AVAILABLE = "pod-group.scheduling.sigs.k8s.io/min-available"


def _carried(pod: dict, key: str) -> str:
    meta = pod["metadata"]
    return (meta.get("labels") or {}).get(key) \
        or (meta.get("annotations") or {}).get(key) or ""


def final_state(nodes: list, pods: list, ctx: dict) -> list:
    groups: dict = {}   # (namespace, group) -> [bound, min-available]
    for p in pods:
        name = _carried(p, GROUP)
        if not name:
            continue
        g = groups.setdefault(
            (p["metadata"].get("namespace", "default"), name), [0, 0])
        g[0] += 1 if (p.get("spec") or {}).get("nodeName") else 0
        g[1] = max(g[1], int(_carried(p, MIN_AVAILABLE) or 0))
    return [f"pod group {ns}/{name}: {bound} members bound, min-available "
            f"{least}" for (ns, name), (bound, least) in groups.items()
            if 0 < bound < least]
