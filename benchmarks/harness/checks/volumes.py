"""Volumes: upstream's four volume predicates, written straight over v1 dicts
(it imports nothing of the program and takes nothing the program has made).

  nodes_over_volume_limit          MaxCSIVolumeCount and the in-tree
      max-volume-count family: DISTINCT attachable volumes a driver on a node
      (two pods sharing one volume count once) over the node's limit for that
      driver: its CSINode's `spec.drivers[].allocatable.count`, else its
      `status.allocatable["attachable-volumes-csi-<driver>"]`
      (`attachable-volumes-aws-ebs`, `-gce-pd`, `-azure-disk` in tree)
  volume_conflicts                 NoDiskConflict: two pods on one node that
      mount one disk DIRECTLY (a GCE PD, RBD image or iSCSI target unless
      both read-only; an EBS volume always). Upstream's isVolumeConflict
      never follows a claim, and neither does this
  pods_bound_off_their_pv_topology CheckVolumeBinding + NoVolumeZoneConflict:
      a pod on a node outside a PV's `spec.nodeAffinity.required`, or whose
      zone / region label is not among the PV's
  pods_bound_with_unbound_claims   a pod bound while a claim of its own is
      missing or has no volume (an unbound Immediate claim makes the pod
      wait; a WaitForFirstConsumer claim is bound at placement)

Which PV a claim is bound to: `ctx["volumes"]`, {"pvs", "pvcs", "csinodes"}
as lists of v1 dicts, where the caller has the objects (the tier-1 tests);
else the configuration's `volume_rule`, by which the claim `pvc-<x>` is
bound to the PV `pv-<x>` of the rule's CSI driver with the volume handle
`vol-<x>`, no node affinity and no zone (shapes/pv_pods.py makes exactly
those).

`counts()` gives the four over a listing (the final state; the wiring
`local_pv` publishes them by name beside `node_volume_state_wrong`, which
holds the program's own resident state to `attached_counts()`); `replay`
holds each Binding to the four in the world as it stood at its turn.
"""

from __future__ import annotations

NAMES = (None, "volume_bindings_refused_at_their_turn")

COUNTS = ("nodes_over_volume_limit", "volume_conflicts",
          "pods_bound_off_their_pv_topology",
          "pods_bound_with_unbound_claims")

LIMIT_PREFIX = "attachable-volumes-"
ZONE_LABELS = ("failure-domain.beta.kubernetes.io/zone",
               "failure-domain.beta.kubernetes.io/region",
               "topology.kubernetes.io/zone",
               "topology.kubernetes.io/region")
#: pod or PV source -> (limit family, id field, a second mount on the node:
#: "ro-ok" conflicts unless both read-only, "exclusive" always, "shared" never)
_SOURCES = {"gcePersistentDisk": ("kubernetes.io/gce-pd", "pdName", "ro-ok"),
            "awsElasticBlockStore": ("kubernetes.io/aws-ebs", "volumeID",
                                     "exclusive"),
            "rbd": ("kubernetes.io/rbd", None, "ro-ok"),
            "iscsi": ("kubernetes.io/iscsi", "iqn", "ro-ok"),
            "azureDisk": ("kubernetes.io/azure-disk", "diskName", "shared")}


def _source(holder: dict):
    """(driver, volume id, sharing rule, read-only) of the attachable source
    in a pod's volume or a PV's spec, or None."""
    src = holder.get("csi")
    if src and "volumeHandle" in src:
        return src.get("driver", ""), src["volumeHandle"], "shared", True
    for key, (driver, field, rule) in _SOURCES.items():
        src = holder.get(key)
        if src:
            vid = src[field] if field else \
                f"{src.get('pool', 'rbd')}/{src.get('image', '')}"
            return driver, vid, rule, bool(src.get("readOnly", False))
    return None


class Volumes:
    """The claims and PVs a run's pods name, by the objects or by the rule."""

    def __init__(self, ctx: dict):
        given = ctx.get("volumes")
        self.rule = None if given else (ctx.get("cfg") or {}).get(
            "volume_rule")
        given = given or {}
        self.pvs = {p["metadata"]["name"]: p for p in given.get("pvs", ())}
        self.pvcs = {(c["metadata"].get("namespace", "default"),
                      c["metadata"]["name"]): c
                     for c in given.get("pvcs", ())}
        self.csinodes = {c["metadata"]["name"]: c
                         for c in given.get("csinodes", ())}

    def pv_of_claim(self, namespace: str, claim: str):
        """The PV a claim is bound to, or None (missing, or unbound)."""
        if self.rule is not None:
            pre = self.rule["claim_prefix"]
            if not claim.startswith(pre):
                return None
            tail = claim[len(pre):]
            return {"metadata": {"name": self.rule["pv_prefix"] + tail},
                    "spec": {"csi": {
                        "driver": self.rule["driver"],
                        "volumeHandle": self.rule["handle_prefix"] + tail}}}
        pvc = self.pvcs.get((namespace, claim))
        name = ((pvc or {}).get("spec") or {}).get("volumeName", "")
        return self.pvs.get(name) if name else None

    def limits(self, node: dict) -> dict:
        out = {}
        alloc = (node.get("status") or {}).get("allocatable") or {}
        for key, val in alloc.items():
            if key.startswith(LIMIT_PREFIX):
                what = key[len(LIMIT_PREFIX):]
                out[what[4:] if what.startswith("csi-")
                    else "kubernetes.io/" + what] = int(val)
        csinode = self.csinodes.get(node["metadata"]["name"])
        for drv in ((csinode or {}).get("spec") or {}).get("drivers") or ():
            count = (drv.get("allocatable") or {}).get("count")
            if count is not None:
                out[drv["name"]] = int(count)
        return out


def pod_mounts(pod: dict, vols: Volumes) -> tuple:
    """(attached, direct, pvs, unbound) of one pod: the distinct
    (driver, volume id) it attaches; its DIRECT mounts as (driver, id, rule,
    read-only); the PVs behind its claims; the claims that have none."""
    attached, direct, pvs, unbound = set(), [], [], []
    ns = pod["metadata"].get("namespace", "default")
    for v in (pod.get("spec") or {}).get("volumes") or ():
        ref = v.get("persistentVolumeClaim")
        if ref:
            pv = vols.pv_of_claim(ns, ref.get("claimName", ""))
            if pv is None:
                unbound.append(ref.get("claimName", ""))
                continue
            pvs.append(pv)
            src = _source(pv.get("spec") or {})
            if src:
                attached.add(src[:2])
            continue
        if "csi" in v:
            continue   # an inline CSI volume is ephemeral: nothing attached
        src = _source(v)
        if src:
            attached.add(src[:2])
            direct.append(src)
    return attached, direct, pvs, unbound


def _conflict(a: tuple, b: tuple) -> bool:
    if a[:2] != b[:2] or a[2] == "shared":
        return False
    return a[2] == "exclusive" or not (a[3] and b[3])


def _reaches(pv: dict, node: dict) -> bool:
    labels = node["metadata"].get("labels") or {}
    for key, val in (pv["metadata"].get("labels") or {}).items():
        if key in ZONE_LABELS and key in labels \
                and labels[key] not in val.split("__"):
            return False
    terms = (((pv.get("spec") or {}).get("nodeAffinity") or {}).get(
        "required") or {}).get("nodeSelectorTerms") or ()
    if not terms:
        return True
    for t in terms:   # terms OR; a term ANDs its fields and expressions
        ok = True
        for f in t.get("matchFields") or ():
            if f.get("key") == "metadata.name" and f.get("operator") == "In":
                ok &= node["metadata"]["name"] in (f.get("values") or ())
        for e in t.get("matchExpressions") or ():
            have, op = labels.get(e["key"]), e["operator"]
            vals = e.get("values") or ()
            ok &= {"In": have in vals and have is not None,
                   "NotIn": have is None or have not in vals,
                   "Exists": have is not None,
                   "DoesNotExist": have is None}[op]
        if ok:
            return True
    return False


class World:
    """Pods on nodes with what they attach, kept a Binding at a time."""

    def __init__(self, nodes: list, vols: Volumes):
        self.vols = vols
        self.nodes = {n["metadata"]["name"]: n for n in nodes}
        self.limits = {name: vols.limits(n) for name, n in self.nodes.items()}
        self.on = {name: {} for name in self.nodes}   # node -> pod -> mounts

    def refusals(self, pod: dict, node_name: str) -> list:
        """Why `pod` may not be on `node_name` beside what is there: a list
        of (count's name, text), empty = it may."""
        node = self.nodes.get(node_name)
        if node is None:
            return []
        name = pod["metadata"]["name"]
        attached, direct, pvs, unbound = pod_mounts(pod, self.vols)
        out = []
        if unbound:
            out.append((COUNTS[3], f"pod {name} on {node_name}: claims "
                        f"{unbound} have no volume"))
        if not all(_reaches(pv, node) for pv in pvs):
            out.append((COUNTS[2], f"pod {name} on {node_name}: outside a "
                        "PV's node affinity or zone"))
        others = [m for p, m in self.on[node_name].items() if p != name]
        if any(_conflict(a, b) for _, od, _, _ in others for a in od
               for b in direct):
            out.append((COUNTS[1], f"pod {name} on {node_name}: a disk of "
                        "its own is mounted there by another pod"))
        if attached:
            there = set().union(*(m[0] for m in others)) if others else set()
            new = attached - there
            for driver, limit in self.limits[node_name].items():
                if any(d == driver for d, _ in new) and sum(
                        1 for d, _ in there | new if d == driver) > limit:
                    out.append((COUNTS[0], f"node {node_name}: pod {name} "
                                f"brings {driver} over its limit {limit}"))
        return out

    def place(self, pod: dict, node_name: str) -> None:
        if node_name in self.on:
            self.on[node_name][pod["metadata"]["name"]] = pod_mounts(
                pod, self.vols)

    def remove(self, name: str) -> None:
        for pods in self.on.values():
            pods.pop(name, None)


def _bound(pods: list) -> list:
    return [p for p in pods if (p.get("spec") or {}).get("nodeName")]


def counts(nodes: list, pods: list, ctx: dict) -> dict:
    """The four counts over a listing, by name: nodes for the limit, pairs
    of pods for conflicts, pods for the other two."""
    vols = Volumes(ctx)
    world = World(nodes, vols)
    out = {name: [] for name in COUNTS}
    for p in _bound(pods):   # a pair that conflicts shows once: at the later
        node_name = p["spec"]["nodeName"]
        for kind, text in world.refusals(p, node_name):
            if kind != COUNTS[0]:   # the limit is a node's, counted below
                out[kind].append(text)
        world.place(p, node_name)
    for node_name, per_driver in attached_by_driver(world).items():
        for driver, limit in world.limits[node_name].items():
            if per_driver.get(driver, 0) > limit:
                out[COUNTS[0]].append(
                    f"node {node_name}: {per_driver[driver]} volumes of "
                    f"{driver}, limit {limit}")
    return out


def attached_by_driver(world: World) -> dict:
    out = {}
    for node_name, pods in world.on.items():
        per = {}
        for driver, _ in set().union(*(m[0] for m in pods.values())) \
                if pods else ():
            per[driver] = per.get(driver, 0) + 1
        out[node_name] = per
    return out


def attached_counts(nodes: list, pods: list, ctx: dict) -> dict:
    """{node: distinct attachable volumes on it}, over a listing: what the
    scheduler's own per-node volume state must add up to."""
    world = World(nodes, Volumes(ctx))
    for p in _bound(pods):
        world.place(p, p["spec"]["nodeName"])
    return {n: sum(per.values())
            for n, per in attached_by_driver(world).items()}


def final_state(nodes: list, pods: list, ctx: dict) -> list:
    return [text for items in counts(nodes, pods, ctx).values()
            for text in items]


def replay(nodes: list, prebound: list, history: list, by_name: dict,
           shapes: list, ctx: dict) -> tuple:
    world = World(nodes, Volumes(ctx))
    for p in prebound:
        world.place(p, p["spec"]["nodeName"])
    looked, bad = 0, []
    for what, name, node_name in history:
        if what == "deleted":
            world.remove(name)
            continue
        pod = by_name.get(name)
        if pod is None:
            continue   # warm-up's throw-away pods
        looked += 1
        bad.extend(text for _kind, text in world.refusals(pod, node_name))
        world.place(pod, node_name)
    return looked, bad
