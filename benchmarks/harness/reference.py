"""The plain reference: the scheduling guarantees a configuration states,
written straight over v1 dicts. It imports nothing of the program.

Two checks, both exact (limit 0 violations):

  final_state   over what the apiserver lists when the run is over: no node
                over its allocatable (cpu, memory, pods); no required
                anti-affinity term sharing its domain with a matching pod;
                every required affinity term with a matching pod in its
                domain; every DoNotSchedule spread constraint within maxSkew
                (only where nothing was deleted: a deletion can widen a skew
                that was legal when each pod was placed).
  replay        the client's watch history, in the order the Bindings
                landed: each Binding is held against the predicates
                (PodFitsResources, MatchInterPodAffinity both directions,
                EvenPodsSpread) in the world as it stood at that moment.

Selectors are the apiserver's label selectors (matchLabels and
matchExpressions In / NotIn / Exists / DoesNotExist). Pods of these
configurations carry no node selector, taint, port or volume, so those
predicates are not restated here; a configuration that adds them adds them
here first.
"""

from __future__ import annotations

_SUFFIX = {"Ki": 1, "Mi": 1 << 10, "Gi": 1 << 20, "Ti": 1 << 30}


def milli_cpu(q: str) -> int:
    q = str(q)
    return int(q[:-1]) if q.endswith("m") else int(float(q) * 1000)


def kib(q: str) -> int:
    q = str(q)
    for suf, mul in _SUFFIX.items():
        if q.endswith(suf):
            return int(q[:-2]) * mul
    return int(q) // 1024


def requests(pod: dict) -> tuple:
    cpu = mem = 0
    for c in pod["spec"].get("containers", ()):
        r = (c.get("resources") or {}).get("requests") or {}
        cpu += milli_cpu(r.get("cpu", "0"))
        mem += kib(r.get("memory", "0Ki"))
    return cpu, mem


def selector_key(sel: dict) -> tuple:
    """A hashable normal form of a label selector."""
    reqs = [(k, "In", (v,)) for k, v in (sel.get("matchLabels") or {}).items()]
    for e in sel.get("matchExpressions") or ():
        reqs.append((e["key"], e["operator"], tuple(e.get("values") or ())))
    return tuple(sorted(reqs))


def matches(skey: tuple, labels: dict) -> bool:
    for key, op, values in skey:
        have = labels.get(key)
        if op == "In":
            ok = have is not None and have in values
        elif op == "NotIn":
            ok = have is None or have not in values
        elif op == "Exists":
            ok = have is not None
        elif op == "DoesNotExist":
            ok = have is None
        else:
            raise ValueError(f"selector operator {op!r}")
        if not ok:
            return False
    return True


def _terms(pod: dict, kind: str) -> list:
    aff = (pod["spec"].get("affinity") or {}).get(kind) or {}
    return [(selector_key(t["labelSelector"]), t["topologyKey"]) for t in
            aff.get("requiredDuringSchedulingIgnoredDuringExecution") or ()]


def _spreads(pod: dict) -> list:
    return [(selector_key(c["labelSelector"]), c["topologyKey"],
             int(c["maxSkew"]))
            for c in pod["spec"].get("topologySpreadConstraints") or ()
            if c.get("whenUnsatisfiable") == "DoNotSchedule"]


class World:
    """Bound pods on nodes, with the per-(selector, topology key) domain
    counts the predicates need kept incrementally, so holding one Binding to
    the predicates costs the same whatever the cluster's size."""

    def __init__(self, nodes: list, pod_shapes: list):
        """`pod_shapes`: one pod of every shape that will ever be placed;
        their selectors fix which (selector, key) pairs are tracked."""
        self.labels = {n["metadata"]["name"]: n["metadata"].get("labels", {})
                       for n in nodes}
        self.alloc = {}
        for n in nodes:
            a = n["status"]["allocatable"]
            self.alloc[n["metadata"]["name"]] = (
                milli_cpu(a["cpu"]), kib(a["memory"]), int(a["pods"]))
        self.used = {name: [0, 0, 0] for name in self.labels}
        pairs = set()
        for p in pod_shapes:
            pairs.update(_terms(p, "podAffinity"))
            pairs.update(_terms(p, "podAntiAffinity"))
            pairs.update((s, k) for s, k, _ in _spreads(p))
        self.pairs = sorted(pairs)
        # matching pods per domain, and pods HOLDING an anti term per domain
        self.count = {pr: {} for pr in self.pairs}
        self.total = {pr: 0 for pr in self.pairs}
        self.anti_holders = {pr: {} for pr in self.pairs}
        self.domains = {}
        for _s, key in self.pairs:
            self.domains.setdefault(key, sorted(
                {lb[key] for lb in self.labels.values() if key in lb}))
        self._match_cache: dict = {}
        self.placed: dict = {}   # pod name -> (node, pod)

    def _matching_pairs(self, labels: dict) -> list:
        lk = tuple(sorted(labels.items()))
        got = self._match_cache.get(lk)
        if got is None:
            got = [pr for pr in self.pairs if matches(pr[0], labels)]
            self._match_cache[lk] = got
        return got

    def _bump(self, pod: dict, node: str, sign: int) -> None:
        cpu, mem = requests(pod)
        u = self.used[node]
        u[0] += sign * cpu
        u[1] += sign * mem
        u[2] += sign
        nl = self.labels[node]
        for pr in self._matching_pairs(pod["metadata"].get("labels", {})):
            dom = nl.get(pr[1])
            if dom is not None:
                c = self.count[pr]
                c[dom] = c.get(dom, 0) + sign
                self.total[pr] += sign
        for pr in _terms(pod, "podAntiAffinity"):
            dom = nl.get(pr[1])
            if dom is not None:
                h = self.anti_holders[pr]
                h[dom] = h.get(dom, 0) + sign

    def add(self, pod: dict, node: str) -> None:
        self.placed[pod["metadata"]["name"]] = (node, pod)
        self._bump(pod, node, +1)

    def remove(self, name: str) -> None:
        got = self.placed.pop(name, None)
        if got is not None:
            self._bump(got[1], got[0], -1)

    def why_not(self, pod: dict, node: str) -> str:
        """'' when `pod` fits `node` in this world, else the predicate that
        rejects it. The pod itself is not in the world yet."""
        if node not in self.labels:
            return f"unknown node {node}"
        cpu, mem = requests(pod)
        u, a = self.used[node], self.alloc[node]
        if u[0] + cpu > a[0] or u[1] + mem > a[1] or u[2] + 1 > a[2]:
            return "PodFitsResources"
        nl = self.labels[node]
        labels = pod["metadata"].get("labels", {})
        for pr in _terms(pod, "podAntiAffinity"):
            dom = nl.get(pr[1])
            if dom is not None and self.count[pr].get(dom, 0) > 0:
                return "MatchInterPodAffinity: own anti-affinity"
        for pr in self._matching_pairs(labels):
            dom = nl.get(pr[1])
            if dom is not None and self.anti_holders[pr].get(dom, 0) > 0:
                return "MatchInterPodAffinity: an existing pod's anti-affinity"
        for pr in _terms(pod, "podAffinity"):
            dom = nl.get(pr[1])
            if dom is not None and self.count[pr].get(dom, 0) > 0:
                continue
            # the first pod of a self-affine group may land anywhere
            if self.total[pr] == 0 and matches(pr[0], labels) \
                    and dom is not None:
                continue
            return "MatchInterPodAffinity: required affinity"
        for sel, key, skew in _spreads(pod):
            dom = nl.get(key)
            if dom is None:
                return "EvenPodsSpread: node lacks the key"
            c = self.count[(sel, key)]
            low = min(c.get(d, 0) for d in self.domains[key])
            mine = c.get(dom, 0) + (1 if matches(sel, labels) else 0)
            if mine - low > skew:
                return "EvenPodsSpread"
        return ""


def final_state(nodes: list, pods: list, check_spread: bool) -> list:
    """Violations among the bound pods of a listing."""
    bound = [p for p in pods if p["spec"].get("nodeName")]
    world = World(nodes, bound)
    bad = []
    for p in bound:
        if p["spec"]["nodeName"] not in world.labels:
            bad.append(f"{p['metadata']['name']} bound to unknown node "
                       f"{p['spec']['nodeName']}")
        else:
            world.add(p, p["spec"]["nodeName"])
    for name, u in world.used.items():
        a = world.alloc[name]
        for what, have, cap in zip(("cpu", "memory", "pods"), u, a):
            if have > cap:
                bad.append(f"node {name}: {what} {have} > allocatable {cap}")
    for name, (node, p) in world.placed.items():
        nl = world.labels[node]
        labels = p["metadata"].get("labels", {})
        for pr in _terms(p, "podAntiAffinity"):
            dom = nl.get(pr[1])
            others = world.count[pr].get(dom, 0) \
                - (1 if matches(pr[0], labels) else 0)
            if dom is not None and others > 0:
                bad.append(f"anti-affinity: {name} shares {pr[1]}={dom} "
                           f"with {others} matching pods")
        for pr in _terms(p, "podAffinity"):
            dom = nl.get(pr[1])
            others = world.count[pr].get(dom, 0) \
                - (1 if matches(pr[0], labels) else 0)
            if dom is None or others <= 0:
                bad.append(f"affinity: {name} has no matching pod in "
                           f"{pr[1]}={dom}")
    if check_spread:
        seen = set()
        for _name, (_node, p) in world.placed.items():
            for sel, key, skew in _spreads(p):
                if (sel, key, skew) in seen:
                    continue
                seen.add((sel, key, skew))
                c = world.count[(sel, key)]
                counts = [c.get(d, 0) for d in world.domains[key]]
                if counts and max(counts) - min(counts) > skew:
                    bad.append(f"spread: {sel} over {key}: max "
                               f"{max(counts)} - min {min(counts)} > "
                               f"maxSkew {skew}")
    return bad


def replay(nodes: list, prebound: list, history: list, pods_by_name: dict,
           shapes: list) -> tuple:
    """Hold every Binding of the watch history to the predicates at its turn.
    `history`: ("bound", name, node) and ("deleted", name, "") in the order
    the client's watch delivered them. Returns (bindings checked, violations).
    """
    world = World(nodes, shapes)
    for p in prebound:
        world.add(p, p["spec"]["nodeName"])
    checked, bad = 0, []
    for what, name, node in history:
        if what == "deleted":
            world.remove(name)
            continue
        pod = pods_by_name.get(name)
        if pod is None or name in world.placed:
            continue   # not a pod of this run / already there (prebound)
        checked += 1
        why = world.why_not(pod, node)
        if why:
            bad.append(f"{name} -> {node}: {why}")
        world.add(pod, node)
    return checked, bad
