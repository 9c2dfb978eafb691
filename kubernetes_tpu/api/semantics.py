"""Executable specification of the reference scheduler's matching semantics.

Pure-Python, pod-at-a-time re-statement of the predicate/priority semantics in
`pkg/scheduler/algorithm/predicates/predicates.go` and
`staging/src/k8s.io/apimachinery/pkg/labels/selector.go`. This module is the
*oracle*: the tensorized device kernels in `kubernetes_tpu.ops` are golden-tested
bit-for-bit against it (mirroring how the reference table-tests predicates).

It is intentionally slow and obvious. Nothing here runs on the hot path.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .types import (
    Affinity,
    HostPort,
    LabelSelector,
    Node,
    NodeSelector,
    NodeSelectorTerm,
    Op,
    Pod,
    PodAffinityTerm,
    Requirement,
    Resources,
    Taint,
    TaintEffect,
    Toleration,
    TolerationOp,
    TopologySpreadConstraint,
    UnsatisfiableAction,
)

# --------------------------------------------------------------------------- #
# labels.Requirement.Matches — apimachinery labels/selector.go:192-215
# --------------------------------------------------------------------------- #


def requirement_matches(req: Requirement, labels: Dict[str, str]) -> bool:
    has = req.key in labels
    if req.op == Op.IN:
        return has and labels[req.key] in req.values
    if req.op == Op.NOT_IN:
        # selector.go:199-203 — absent key satisfies NotIn
        return (not has) or labels[req.key] not in req.values
    if req.op == Op.EXISTS:
        return has
    if req.op == Op.DOES_NOT_EXIST:
        return not has
    if req.op in (Op.GT, Op.LT):
        # selector.go:208-233 — key must exist, both sides parse as int64
        if not has:
            return False
        try:
            lhs = int(labels[req.key])
            rhs = int(req.values[0])
        except (ValueError, IndexError):
            return False
        return lhs > rhs if req.op == Op.GT else lhs < rhs
    raise AssertionError(req.op)


def selector_matches(sel: LabelSelector, labels: Dict[str, str]) -> bool:
    """Empty selector matches everything (labels.Everything)."""
    return all(requirement_matches(r, labels) for r in sel.requirements)


def node_selector_term_matches(term: NodeSelectorTerm, node: Node) -> bool:
    """v1helper.MatchNodeSelectorTerms: empty term matches nothing; matchFields
    only supports metadata.name."""
    if not term.requirements and not term.field_name_in:
        return False
    for req in term.requirements:
        if not requirement_matches(req, node.labels):
            return False
    if term.field_name_in and node.name not in term.field_name_in:
        return False
    return True


def node_selector_matches(ns: NodeSelector, node: Node) -> bool:
    """OR of terms; empty term list matches nothing."""
    return any(node_selector_term_matches(t, node) for t in ns.terms)


# --------------------------------------------------------------------------- #
# Predicates
# --------------------------------------------------------------------------- #


def pod_matches_node_selector(pod: Pod, node: Node) -> bool:
    """PodMatchNodeSelector → podMatchesNodeSelectorAndAffinityTerms
    (predicates.go:867-914): spec.nodeSelector AND node-affinity required."""
    for k, v in pod.node_selector.items():
        if node.labels.get(k) != v:
            return False
    if pod.affinity.node_required is not None:
        # nil RequiredDuringScheduling ⇒ match; non-nil delegates to
        # MatchNodeSelectorTerms (predicates.go:894-906)
        if not node_selector_matches(pod.affinity.node_required, node):
            return False
    return True


def pod_fits_host(pod: Pod, node: Node) -> bool:
    """PodFitsHost (predicates.go:926-935)."""
    return not pod.node_name or pod.node_name == node.name


def pod_fits_resources(
    pod: Pod, node: Node, used: Resources, used_pods: int
) -> Tuple[bool, List[str]]:
    """PodFitsResources (predicates.go:789-845): pods count, CPU, memory,
    ephemeral storage, then every scalar resource."""
    alloc = node.allocatable
    fails: List[str] = []
    if used_pods + 1 > alloc.pods:
        fails.append("pods")
    req = pod.requests
    if req.milli_cpu == 0 and req.memory_kib == 0 and req.ephemeral_kib == 0 and not req.scalars:
        return (not fails, fails)
    if req.milli_cpu > alloc.milli_cpu - used.milli_cpu:
        fails.append("cpu")
    if req.memory_kib > alloc.memory_kib - used.memory_kib:
        fails.append("memory")
    if req.ephemeral_kib > alloc.ephemeral_kib - used.ephemeral_kib:
        fails.append("ephemeral-storage")
    used_scalars = dict(used.scalars)
    alloc_scalars = dict(alloc.scalars)
    for name, amount in req.scalars:
        if amount > alloc_scalars.get(name, 0) - used_scalars.get(name, 0):
            fails.append(name)
    return (not fails, fails)


def tolerates_taint(tol: Toleration, taint: Taint) -> bool:
    """v1helper Toleration.ToleratesTaint."""
    if tol.effect is not None and tol.effect != taint.effect:
        return False
    if tol.key and tol.key != taint.key:
        return False
    # empty key with Exists matches all keys
    if tol.op == TolerationOp.EXISTS:
        return True
    return tol.value == taint.value


def pod_tolerates_node_taints(pod: Pod, node: Node) -> bool:
    """PodToleratesNodeTaints (predicates.go:1543-1549): only NoSchedule and
    NoExecute taints filter; PreferNoSchedule is score-only."""
    for taint in node.taints:
        if taint.effect == TaintEffect.PREFER_NO_SCHEDULE:
            continue
        if not any(tolerates_taint(t, taint) for t in pod.tolerations):
            return False
    return True


def _port_conflict(a: HostPort, b: HostPort) -> bool:
    """HostPortInfo conflict: same protocol+port, and IPs equal or either is
    wildcard (node_info.go hostPortInfo.CheckConflict)."""
    if a.protocol != b.protocol or a.port != b.port:
        return False
    wild = ("", "0.0.0.0")
    return a.host_ip in wild or b.host_ip in wild or a.host_ip == b.host_ip


def pod_fits_host_ports(pod: Pod, node_used_ports: Sequence[HostPort]) -> bool:
    """PodFitsHostPorts (predicates.go:1104-1120)."""
    for want in pod.host_ports:
        if want.port == 0:
            continue
        if any(_port_conflict(want, have) for have in node_used_ports):
            return False
    return True


def check_node_unschedulable(pod: Pod, node: Node) -> bool:
    """CheckNodeUnschedulablePredicate (predicates.go:1522-1541): node.spec
    .unschedulable blocks unless tolerated (key node.kubernetes.io/unschedulable,
    effect NoSchedule)."""
    if not node.unschedulable:
        return True
    fake = Taint(key="node.kubernetes.io/unschedulable", effect=TaintEffect.NO_SCHEDULE)
    return any(tolerates_taint(t, fake) for t in pod.tolerations)


# --------------------------------------------------------------------------- #
# Inter-pod affinity — predicates.go:1212-1520
# --------------------------------------------------------------------------- #


def term_namespaces(term: PodAffinityTerm, owner: Pod) -> Tuple[str, ...]:
    """GetNamespacesFromPodAffinityTerm: empty ⇒ the owner pod's namespace."""
    return term.namespaces if term.namespaces else (owner.namespace,)


def term_matches_pod(term: PodAffinityTerm, owner: Pod, other: Pod) -> bool:
    """PodMatchesTermsNamespaceAndSelector."""
    if other.namespace not in term_namespaces(term, owner):
        return False
    return selector_matches(term.selector, other.labels)


def interpod_affinity_fits(
    pod: Pod,
    node: Node,
    nodes_by_name: Dict[str, Node],
    existing: Sequence[Pod],
) -> bool:
    """InterPodAffinityMatches (predicates.go:1212-1260) for one candidate node:
      1. every required affinity term has ≥1 matching existing pod in the same
         topology domain — OR matches the incoming pod itself (the self-match
         rule, predicates.go:1438-1461);
      2. no required anti-affinity term of the incoming pod matches any existing
         pod in-domain (predicates.go:1463-1487);
      3. no existing pod has a required anti-affinity term matching the incoming
         pod in-domain (symmetry, satisfiesExistingPodsAntiAffinity :1319-1360).
    Pods on nodes lacking the topology key are never in-domain."""

    def in_domain(other_node_name: str, topology_key: str) -> bool:
        other = nodes_by_name.get(other_node_name)
        if other is None or topology_key not in node.labels or topology_key not in other.labels:
            return False
        return node.labels[topology_key] == other.labels[topology_key]

    # 1. required affinity: every term needs ≥1 matching existing pod in the
    # candidate's topology domain (nodeMatchesAllTopologyTerms). Escape hatch
    # (predicates.go:1436-1440): if NO existing pod on a keyed node matches ANY
    # term (the potential-affinity map is empty) and the pod matches all its
    # own terms, the pod passes on every node — no node-label condition.
    if pod.affinity.pod_required:
        def keyed(ex: Pod, topology_key: str) -> bool:
            exn = nodes_by_name.get(ex.node_name)
            return exn is not None and topology_key in exn.labels

        all_terms_hit = all(
            any(
                term_matches_pod(term, pod, ex) and in_domain(ex.node_name, term.topology_key)
                for ex in existing
            )
            for term in pod.affinity.pod_required
        )
        if not all_terms_hit:
            map_empty = not any(
                term_matches_pod(term, pod, ex) and keyed(ex, term.topology_key)
                for term in pod.affinity.pod_required
                for ex in existing
            )
            self_all = all(
                term_matches_pod(term, pod, pod) for term in pod.affinity.pod_required
            )
            if not (map_empty and self_all):
                return False
    # 2. incoming pod's anti-affinity vs existing pods (no escape hatch)
    for term in pod.affinity.anti_required:
        for ex in existing:
            if term_matches_pod(term, pod, ex) and in_domain(ex.node_name, term.topology_key):
                return False
    # 3. existing pods' anti-affinity vs incoming pod (symmetry)
    for ex in existing:
        for term in ex.affinity.anti_required:
            if term_matches_pod(term, ex, pod) and in_domain(ex.node_name, term.topology_key):
                return False
    return True


# --------------------------------------------------------------------------- #
# Pod topology spread (EvenPodsSpread) — predicates.go:1643-1703, metadata.go
# --------------------------------------------------------------------------- #


def topology_spread_fits(
    pod: Pod,
    node: Node,
    nodes: Sequence[Node],
    existing: Sequence[Pod],
) -> bool:
    """EvenPodsSpreadPredicate for hard (DoNotSchedule) constraints.

    For each constraint: candidate node must carry the topology key; the match
    count on the candidate's topology value, plus this pod (selfMatch,
    metadata.go podSpreadCache semantics), minus the global minimum match count
    over eligible topology values, must be ≤ maxSkew. Eligible values are those
    of nodes that pass the pod's nodeSelector/affinity *and* carry the key
    (metadata.go:114-176 — nodes are pre-filtered by PodMatchesNodeSelectorAndAffinityTerms)."""
    hard = [c for c in pod.topology_spread if c.when_unsatisfiable == UnsatisfiableAction.DO_NOT_SCHEDULE]
    if not hard:
        return True
    for c in hard:
        if c.topology_key not in node.labels:
            return False
        counts: Dict[str, int] = {}
        for n in nodes:
            if c.topology_key not in n.labels:
                continue
            if not pod_matches_node_selector(pod, n):
                continue
            counts.setdefault(n.labels[c.topology_key], 0)
        for ex in existing:
            ex_node = next((n for n in nodes if n.name == ex.node_name), None)
            if ex_node is None or c.topology_key not in ex_node.labels:
                continue
            val = ex_node.labels[c.topology_key]
            if val not in counts or not pod_matches_node_selector(pod, ex_node):
                # only pods ON eligible nodes count (metadata.go processNode
                # returns before counting a node the pod's selector rejects),
                # even where an eligible node shares the topology value
                continue
            if ex.namespace == pod.namespace and selector_matches(c.selector, ex.labels):
                counts[val] += 1
        if not counts:
            # empty eligible-domain map ⇒ the constraint passes everywhere
            # (predicates.go:1661-1663: len(tpPairToMatchNum)==0 → true)
            continue
        self_match = 1 if selector_matches(c.selector, pod.labels) else 0
        val = node.labels[c.topology_key]
        # a pair absent from the map reads as matchNum 0 (Go map zero value)
        match_num = counts.get(val, 0)
        min_count = min(counts.values())
        if match_num + self_match - min_count > c.max_skew:
            return False
    return True


# --------------------------------------------------------------------------- #
# Priorities (scores) — pkg/scheduler/algorithm/priorities/
# --------------------------------------------------------------------------- #

MAX_NODE_SCORE = 100  # framework/v1alpha1/interface.go:87


def _fraction(req: int, cap: int) -> float:
    return 0.0 if cap == 0 else req / cap


def least_requested_score(req: Resources, used: Resources, alloc: Resources) -> int:
    """least_requested.go: ((cap-req)*MaxNodeScore/cap averaged over cpu+mem)."""

    def per(reqv: int, usedv: int, capv: int) -> int:
        total = usedv + reqv
        if capv == 0 or total > capv:
            return 0
        return ((capv - total) * MAX_NODE_SCORE) // capv

    return (
        per(req.milli_cpu, used.milli_cpu, alloc.milli_cpu)
        + per(req.memory_kib, used.memory_kib, alloc.memory_kib)
    ) // 2


def most_requested_score(req: Resources, used: Resources, alloc: Resources) -> int:
    """most_requested.go: (total*MaxNodeScore/cap averaged over cpu+mem)."""

    def per(reqv: int, usedv: int, capv: int) -> int:
        total = usedv + reqv
        if capv == 0 or total > capv:
            return 0
        return (total * MAX_NODE_SCORE) // capv

    return (
        per(req.milli_cpu, used.milli_cpu, alloc.milli_cpu)
        + per(req.memory_kib, used.memory_kib, alloc.memory_kib)
    ) // 2


def balanced_allocation_score(req: Resources, used: Resources, alloc: Resources) -> int:
    """balanced_resource_allocation.go: 100 - |cpuFraction-memFraction|*100
    (two-resource variant; volume fraction off by default)."""
    cpu = _fraction(used.milli_cpu + req.milli_cpu, alloc.milli_cpu)
    mem = _fraction(used.memory_kib + req.memory_kib, alloc.memory_kib)
    if cpu >= 1 or mem >= 1:
        return 0
    return int(100 - abs(cpu - mem) * 100)


def _resource_amount(res: Resources, name: str) -> int:
    fixed = {"cpu": res.milli_cpu, "memory": res.memory_kib,
             "ephemeral-storage": res.ephemeral_kib}
    return fixed[name] if name in fixed else dict(res.scalars).get(name, 0)


def broken_linear(shape: Sequence[Tuple[int, int]], p: int) -> int:
    """buildBrokenLinearFunction (requested_to_capacity_ratio.go) through
    `shape`'s (utilization, score) points, utilization ascending; Go's
    integer division truncates toward zero."""
    for i, (x, y) in enumerate(shape):
        if p <= x:
            if i == 0:
                return y
            x0, y0 = shape[i - 1]
            num = (y - y0) * (p - x0)
            q = abs(num) // (x - x0)
            return y0 + (q if num >= 0 else -q)
    return shape[-1][1]


def requested_to_capacity_ratio_score(
    req: Resources, used: Resources, alloc: Resources,
    shape: Sequence[Tuple[int, int]] = ((0, 0), (100, 100)),
    resources: Sequence[Tuple[str, int]] = (("cpu", 1), ("memory", 1)),
) -> int:
    """requested_to_capacity_ratio.go (v1.17), 0..100: per resource of the
    weight map `100 - (cap - total) * 100 / cap` in integers (100 where the
    node has none of it or total > cap; a pod that asks no cpu / no memory
    counts the non-zero defaults 100m / 200Mi), through the shape (scores
    already on the 0..100 scale), then the weighted mean over the resources
    whose score is positive, math.Round-ed. `used` is what the node's pods
    ask (this repo counts a bound pod's own requests, not their non-zero
    defaults: docs/PARITY.md 6)."""
    num = den = 0
    for name, weight in resources:
        r = _resource_amount(req, name)
        if r == 0 and name == "cpu":
            r = 100
        if r == 0 and name == "memory":
            r = 200 * 1024
        total = _resource_amount(used, name) + r
        cap = _resource_amount(alloc, name)
        util = 100 if cap == 0 or total > cap \
            else 100 - (cap - total) * 100 // cap
        s = broken_linear(shape, util)
        if s > 0:
            num += s * weight
            den += weight
    return 0 if den == 0 else (2 * num + den) // (2 * den)


def taint_toleration_score(pod: Pod, node: Node) -> int:
    """taint_toleration.go: count of intolerable PreferNoSchedule taints,
    reduced to 0..100 (fewer = better) by reduce (max-normalized elsewhere);
    here we return the raw intolerable count for the kernel golden test."""
    count = 0
    for taint in node.taints:
        if taint.effect != TaintEffect.PREFER_NO_SCHEDULE:
            continue
        if not any(tolerates_taint(t, taint) for t in pod.tolerations):
            count += 1
    return count


def node_affinity_score(pod: Pod, node: Node) -> int:
    """node_affinity.go CalculateNodeAffinityPriorityMap: sum of weights of
    matching preferred terms (raw, reduce normalizes)."""
    total = 0
    for pref in pod.affinity.node_preferred:
        if pref.weight == 0:
            continue
        if node_selector_term_matches(pref.term, node):
            total += pref.weight
    return total


def no_disk_conflict(pod: Pod, node_pods: Sequence[Pod]) -> bool:
    """NoDiskConflict (predicates.go:156-221): same (driver, volume) on one
    node conflicts unless both mounts are read-only."""
    for v in pod.volumes:
        for ex in node_pods:
            for ev in ex.volumes:
                if v.driver == ev.driver and v.vol_id == ev.vol_id \
                        and not (v.read_only and ev.read_only):
                    return False
    return True


def max_volume_count_fits(pod: Pod, node: Node,
                          node_pods: Sequence[Pod]) -> bool:
    """Max attachable volumes per driver (csi_volume_predicate.go:89-160):
    distinct volumes already attached plus the pod's new distinct volumes
    must stay within Node.volume_limits[driver] (absent = unlimited)."""
    if not pod.volumes or not node.volume_limits:
        return True
    attached: Dict[str, set] = {}
    for ex in node_pods:
        for ev in ex.volumes:
            attached.setdefault(ev.driver, set()).add(ev.vol_id)
    for v in pod.volumes:
        attached.setdefault(v.driver, set()).add(v.vol_id)
    for drv, lim in node.volume_limits.items():
        if lim >= 0 and len(attached.get(drv, ())) > lim:
            return False
    return True


# --------------------------------------------------------------------------- #
# Score parity set (priorities/) — pure-Python references for the tensor
# kernels in ops/scores.py; golden-tested in tests/test_scores.py
# --------------------------------------------------------------------------- #

DEFAULT_HARD_POD_AFFINITY_WEIGHT = 1
IMG_MIN_KIB = 23 * 1024
IMG_MAX_KIB = 1000 * 1024
ZONE_WEIGHTING = 2.0 / 3.0
ZONE_LABELS = ("topology.kubernetes.io/zone",
               "failure-domain.beta.kubernetes.io/zone")


def _same_domain(a: Node, b: Node, key: str) -> bool:
    return key in a.labels and key in b.labels and a.labels[key] == b.labels[key]


def interpod_preferred_raw(
    pod: Pod,
    node: Node,
    nodes_by_name: Dict[str, Node],
    existing: Sequence[Pod],
    hard_weight: int = DEFAULT_HARD_POD_AFFINITY_WEIGHT,
) -> float:
    """Raw (un-normalized) preferred inter-pod affinity count for one candidate
    node — all four directions of interpod_affinity.go:119-215:
      + pod's preferred terms matching existing pods in-domain,
      − pod's preferred anti terms,
      + existing pods' REQUIRED affinity terms matching the pod × hard_weight,
      + existing pods' preferred terms matching the pod,
      − existing pods' preferred anti terms matching the pod."""
    raw = 0.0
    for ex in existing:
        exn = nodes_by_name.get(ex.node_name)
        if exn is None:
            continue
        for w in pod.affinity.pod_preferred:
            if term_matches_pod(w.term, pod, ex) and _same_domain(
                    node, exn, w.term.topology_key):
                raw += w.weight
        for w in pod.affinity.anti_preferred:
            if term_matches_pod(w.term, pod, ex) and _same_domain(
                    node, exn, w.term.topology_key):
                raw -= w.weight
        for term in ex.affinity.pod_required:
            if term_matches_pod(term, ex, pod) and _same_domain(
                    node, exn, term.topology_key):
                raw += hard_weight
        for w in ex.affinity.pod_preferred:
            if term_matches_pod(w.term, ex, pod) and _same_domain(
                    node, exn, w.term.topology_key):
                raw += w.weight
        for w in ex.affinity.anti_preferred:
            if term_matches_pod(w.term, ex, pod) and _same_domain(
                    node, exn, w.term.topology_key):
                raw -= w.weight
    return raw


def interpod_preferred_scores(
    pod: Pod, nodes: Sequence[Node], existing: Sequence[Pod],
    hard_weight: int = DEFAULT_HARD_POD_AFFINITY_WEIGHT,
) -> Dict[str, float]:
    """Min-max normalized 0..100 over all nodes (ops/interpod.py convention:
    the normalization domain is every valid node; constant raw ⇒ 0)."""
    by_name = {n.name: n for n in nodes}
    raw = {n.name: interpod_preferred_raw(pod, n, by_name, existing,
                                          hard_weight) for n in nodes}
    lo, hi = min(raw.values()), max(raw.values())
    if hi <= lo:
        return {n.name: 0.0 for n in nodes}
    return {k: 100.0 * (v - lo) / (hi - lo) for k, v in raw.items()}


def even_spread_soft_scores(
    pod: Pod, nodes: Sequence[Node], existing: Sequence[Pod]
) -> Dict[str, float]:
    """EvenPodsSpread SCORE over ScheduleAnyway constraints
    (even_pods_spread.go:106-227), normalization domain = all eligible nodes
    (docs/PARITY.md)."""
    soft = [c for c in pod.topology_spread
            if int(c.when_unsatisfiable) != 0]
    out = {n.name: 0.0 for n in nodes}
    if not soft:
        return out

    def node_matchable(n: Node) -> bool:
        return pod_matches_node_selector(pod, n)

    def elig(n: Node) -> bool:
        return node_matchable(n) and all(
            c.topology_key in n.labels for c in soft)

    # per (constraint, topo value) matching-pod counts over matchable nodes
    by_name = {n.name: n for n in nodes}
    counts: Dict[Tuple[int, str], int] = {}
    for ci, c in enumerate(soft):
        for ex in existing:
            exn = by_name.get(ex.node_name)
            if exn is None or not node_matchable(exn):
                continue
            if c.topology_key not in exn.labels:
                continue
            if ex.namespace != pod.namespace:
                continue
            if not selector_matches(c.selector, ex.labels):
                continue
            key = (ci, exn.labels[c.topology_key])
            counts[key] = counts.get(key, 0) + 1

    raw = {}
    for n in nodes:
        r = 0
        for ci, c in enumerate(soft):
            if c.topology_key in n.labels:
                r += counts.get((ci, n.labels[c.topology_key]), 0)
        raw[n.name] = r

    elig_nodes = [n for n in nodes if elig(n)]
    if not elig_nodes:
        return out
    total = sum(raw[n.name] for n in elig_nodes)
    mn = min(raw[n.name] for n in elig_nodes)
    denom = total - mn
    for n in elig_nodes:
        out[n.name] = (100.0 * (total - raw[n.name]) / denom
                       if denom > 0 else 100.0)
    return out


def selector_spread_scores(
    pod: Pod, nodes: Sequence[Node], existing: Sequence[Pod]
) -> Dict[str, float]:
    """SelectorSpread (selector_spreading.go:62-165): fewest same-owner pods
    per node, zone-blended 1/3:2/3 when zone labels exist."""
    out = {n.name: 0.0 for n in nodes}
    if not pod.spread_selectors:
        return out

    def matches(ex: Pod) -> bool:
        return ex.namespace == pod.namespace and all(
            selector_matches(s, ex.labels) for s in pod.spread_selectors)

    count = {n.name: 0 for n in nodes}
    for ex in existing:
        if ex.node_name in count and matches(ex):
            count[ex.node_name] += 1

    def zone_of(n: Node):
        for zl in ZONE_LABELS:
            if zl in n.labels:
                return (zl, n.labels[zl])
        return None

    max_n = max(count.values(), default=0)
    zcounts: Dict[tuple, int] = {}
    for n in nodes:
        z = zone_of(n)
        if z is not None:
            zcounts[z] = zcounts.get(z, 0) + count[n.name]
    max_z = max(zcounts.values(), default=0)
    have_zones = bool(zcounts)

    for n in nodes:
        f = 100.0
        if max_n > 0:
            f = 100.0 * (max_n - count[n.name]) / max_n
        z = zone_of(n)
        if have_zones and z is not None:
            zs = 100.0
            if max_z > 0:
                zs = 100.0 * (max_z - zcounts[z]) / max_z
            f = f * (1.0 - ZONE_WEIGHTING) + ZONE_WEIGHTING * zs
        out[n.name] = f
    return out


def image_locality_scores(
    pod: Pod, nodes: Sequence[Node]
) -> Dict[str, float]:
    """ImageLocality (image_locality.go:39-92): sum of spread-scaled sizes of
    the pod's images already present on the node, clamped and scaled."""
    total = max(len(nodes), 1)
    num_nodes = {
        img: sum(1 for n in nodes if img in n.images_kib)
        for n_ in nodes for img in n_.images_kib
    }
    sizes: Dict[str, int] = {}
    for n in nodes:
        for img, s in n.images_kib.items():
            sizes.setdefault(img, s)
    out = {}
    for n in nodes:
        s = 0.0
        for img in pod.images:
            if img in n.images_kib:
                spread = num_nodes.get(img, 0) / total
                s += sizes.get(img, 0) * spread
        s = min(max(s, IMG_MIN_KIB), IMG_MAX_KIB)
        out[n.name] = 100.0 * (s - IMG_MIN_KIB) / (IMG_MAX_KIB - IMG_MIN_KIB)
    return out
