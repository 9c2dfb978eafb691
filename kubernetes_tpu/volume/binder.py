"""Scheduler-coordinated volume binding.

Analog of `pkg/scheduler/volumebinder/volume_binder.go` over
`pkg/controller/volume/scheduling/scheduler_binder.go`, fed from the
scheduler's PVC / PV / StorageClass / node listers:

  * resolve(pod): FindPodVolumes — the pod's claims turned into what the
    device decides on. A bound claim gives the attachable volume behind its
    PV (counted against the node's per-driver limit) and restricts the pod
    to the nodes the PV is reachable from: its `spec.nodeAffinity`
    (CheckVolumeBinding) and its zone / region labels
    (NoVolumeZoneConflict). An unbound WaitForFirstConsumer claim restricts
    the pod to the nodes some matching free PV is reachable from and counts
    as one volume of that PV's driver. An unbound Immediate claim, a claim
    or a PV the listers do not have: the pod waits ("pod has unbound
    immediate PersistentVolumeClaims").
  * volumes_of(pod): the volumes of a pod that is bound already (another
    scheduler's, or this one's before a restart), for the node's count.
  * bind(pod, node): AssumePodVolumes + BindPodVolumes — at placement, bind
    each WaitForFirstConsumer claim to a PV reachable from the chosen node.

The host resolves names and nothing else: the node restriction reaches the
device as a `metadata.name IN (...)` node-affinity term, equal for every
pod whose volumes share a topology (one class a zone, not a pod), and the
volumes as `Pod.volumes` (ops/volumes.py decides conflicts and limits).
"""

from __future__ import annotations

import dataclasses
import threading
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Tuple

from kubernetes_tpu.api.types import (NodeSelector, NodeSelectorTerm, Pod,
                                      VolumeRef)
from kubernetes_tpu.api.v1 import node_names_from_terms, volume_ref_from_pv
from kubernetes_tpu.machinery import labels as mlabels, meta
from kubernetes_tpu.volume.pv_controller import (
    PersistentVolumeController,
    WFFC,
    pv_matches_claim,
)

Obj = dict

#: a PV's labels NoVolumeZoneConflict holds a node to (predicates.go
#: VolumeZoneChecker); a value may be a `__`-separated set of zones
ZONE_LABELS = ("failure-domain.beta.kubernetes.io/zone",
               "failure-domain.beta.kubernetes.io/region",
               "topology.kubernetes.io/zone",
               "topology.kubernetes.io/region")

UNBOUND_IMMEDIATE = "pod has unbound immediate PersistentVolumeClaims"


@dataclass
class VolumeDecision:
    """Outcome of the filter half (FindPodVolumes)."""

    wait: bool = False                 # the pod cannot be decided on yet
    reason: str = ""
    allowed_nodes: Optional[FrozenSet[str]] = None  # None = unrestricted
    wffc_claims: List[Obj] = field(default_factory=list)
    volumes: Tuple[VolumeRef, ...] = ()  # what its claims attach


def restrict_pod_nodes(pod: Pod, allowed: FrozenSet[str]) -> Pod:
    """AND a node-name restriction into the pod's required node affinity by
    adding matchFields(metadata.name IN allowed) to every term (or one fresh
    term) — evaluated on device like any other affinity."""
    names = tuple(sorted(allowed))
    aff = pod.affinity
    if aff.node_required and aff.node_required.terms:
        terms = tuple(
            dataclasses.replace(t, field_name_in=tuple(
                sorted(set(t.field_name_in) & allowed
                       if t.field_name_in else allowed)) or ("",))
            for t in aff.node_required.terms)
    else:
        terms = (NodeSelectorTerm(field_name_in=names or ("",)),)
    pod.affinity = dataclasses.replace(
        aff, node_required=NodeSelector(terms=terms))
    return pod


def resolved_pod(pod: Pod, decision: VolumeDecision) -> Pod:
    """The copy of `pod` a wave encodes: what its claims attach beside what
    it mounts directly, and the node restriction of their PVs."""
    out = dataclasses.replace(pod, volumes=pod.volumes + decision.volumes,
                              unresolved=pod)
    if decision.allowed_nodes is not None:
        restrict_pod_nodes(out, decision.allowed_nodes)
    return out


class SchedulerVolumeBinder:
    """Host-side volume coordination for the scheduler server."""

    def __init__(self, client, pvc_lister, pv_lister, sc_lister, node_lister):
        self.client = client
        self.pvc_lister = pvc_lister
        self.pv_lister = pv_lister
        self.sc_lister = sc_lister
        self.node_lister = node_lister
        self._mu = threading.Lock()
        # PVs this binder bound whose claimRef the PV lister does not show
        # yet (the assume cache's part): pv name -> claim key
        self._assumed: Dict[str, str] = {}
        # nodes a PV is reachable from, by its topology (node-affinity terms,
        # zone labels): every PV of one zone shares an entry; emptied when a
        # node is added, updated or removed
        self._reach: Dict[tuple, Optional[FrozenSet[str]]] = {}

    def nodes_changed(self) -> None:
        if self._reach:
            self._reach = {}

    # -- a PV's topology ------------------------------------------------- #

    def _pv_nodes(self, pv: Obj) -> Optional[FrozenSet[str]]:
        """Nodes a PV is reachable from, None = every node: its
        `spec.nodeAffinity.required` (matchFields names, matchExpressions
        over node labels; terms ORed) and its zone labels (a node that
        carries the label must carry one of the PV's values; a node without
        any of them passes, as upstream's)."""
        spec = pv.get("spec") or {}
        terms = ((spec.get("nodeAffinity") or {}).get("required") or {}
                 ).get("nodeSelectorTerms") or []
        zones = {k: set(v.split("__"))
                 for k, v in (meta.labels_of(pv) or {}).items()
                 if k in ZONE_LABELS}
        if not terms and not zones:
            return None
        key = (repr(terms), tuple(sorted(
            (k, tuple(sorted(v))) for k, v in zones.items())))
        if key in self._reach:
            return self._reach[key]
        nodes = self.node_lister.list()
        allowed: Optional[set] = None
        if terms:
            # a term ANDs its matchFields and matchExpressions; terms OR
            allowed = set()
            for t in terms:
                names = node_names_from_terms([t])
                sel = mlabels.from_label_selector(
                    {"matchExpressions": t["matchExpressions"]}) \
                    if t.get("matchExpressions") else None
                allowed |= {
                    meta.name(n) for n in nodes
                    if (names is None or meta.name(n) in names)
                    and (sel is None or sel.matches(meta.labels_of(n)))}
        if zones:
            in_zone = set()
            for n in nodes:
                labels = meta.labels_of(n) or {}
                if all(labels[k] in vals for k, vals in zones.items()
                       if k in labels):
                    in_zone.add(meta.name(n))
            allowed = in_zone if allowed is None else allowed & in_zone
        out = frozenset(allowed) if allowed is not None else None
        self._reach[key] = out
        return out

    def _is_wffc(self, claim: Obj) -> bool:
        cls = (claim.get("spec") or {}).get("storageClassName", "") or ""
        if not cls:
            return False
        sc = self.sc_lister.get("", cls)
        return bool(sc) and sc.get("volumeBindingMode") == WFFC

    def _free_pvs(self, claim: Obj) -> List[Obj]:
        """Free PVs that match an unbound claim, by name; less those this
        binder has bound since the lister last heard."""
        with self._mu:
            for name in [n for n in self._assumed
                         if ((self.pv_lister.get("", n) or {}).get("spec")
                             or {}).get("claimRef")]:
                del self._assumed[name]   # the lister has caught up
            taken = set(self._assumed)
        return sorted((pv for pv in self.pv_lister.list()
                       if meta.name(pv) not in taken
                       and pv_matches_claim(pv, claim)), key=meta.name)

    # -- the filter half -------------------------------------------------- #

    def resolve(self, pod: Pod) -> VolumeDecision:
        """FindPodVolumes over the pod's `claims`."""
        allowed: Optional[FrozenSet[str]] = None
        vols: List[VolumeRef] = []
        wffc: List[Obj] = []
        get_claim, get_pv = self.pvc_lister.get, self.pv_lister.get
        for ref in pod.claims:
            claim = get_claim(pod.namespace, ref.name)
            if claim is None or meta.is_being_deleted(claim):
                return VolumeDecision(
                    wait=True,
                    reason=f'persistentvolumeclaim "{ref.name}" not found')
            pv_name = (claim.get("spec") or {}).get("volumeName", "")
            if pv_name:
                pv = get_pv("", pv_name)
                if pv is None:
                    return VolumeDecision(
                        wait=True,
                        reason=f'persistentvolume "{pv_name}" not found')
                vol = volume_ref_from_pv(pv)
                if vol is not None:
                    vols.append(vol)
                reach = self._pv_nodes(pv)
            elif self._is_wffc(claim):
                free = self._free_pvs(claim)
                reach = frozenset()
                for pv in free:
                    r = self._pv_nodes(pv)
                    if r is None:
                        reach = None
                        break
                    reach |= r
                vol = next((v for v in map(volume_ref_from_pv, free)
                            if v is not None), None)
                if vol is not None:
                    # which PV it will be is decided at placement; it is one
                    # volume of that driver wherever the pod lands
                    vols.append(VolumeRef(
                        f"claim:{pod.namespace}/{ref.name}", vol.driver,
                        True))
                wffc.append(claim)
            else:
                return VolumeDecision(wait=True, reason=UNBOUND_IMMEDIATE)
            if reach is not None:
                allowed = reach if allowed is None else allowed & reach
        return VolumeDecision(allowed_nodes=allowed, wffc_claims=wffc,
                              volumes=tuple(vols))

    def volumes_of(self, pod: Pod) -> Tuple[VolumeRef, ...]:
        """What a BOUND pod's claims attach to its node, as far as the
        listers know; a claim they cannot follow counts nothing."""
        vols: List[VolumeRef] = []
        for ref in pod.claims:
            claim = self.pvc_lister.get(pod.namespace, ref.name)
            pv_name = ((claim or {}).get("spec") or {}).get("volumeName", "")
            pv = self.pv_lister.get("", pv_name) if pv_name else None
            vol = volume_ref_from_pv(pv) if pv is not None else None
            if vol is not None:
                vols.append(vol)
        return tuple(vols)

    # -- the bind half ---------------------------------------------------- #

    def bind(self, pod: Pod, node_name: str) -> bool:
        """AssumePodVolumes + BindPodVolumes: bind each WaitForFirstConsumer
        claim to a PV reachable from the chosen node. False (the scheduler
        rolls the pod back) if a claim cannot be satisfied there, or the pod
        has come to wait meanwhile. Nothing to do for bound claims."""
        decision = self.resolve(pod)
        if decision.wait:
            return False
        for claim in decision.wffc_claims:
            chosen = None
            for pv in self._free_pvs(claim):
                reach = self._pv_nodes(pv)
                if reach is None or node_name in reach:
                    chosen = pv
                    break
            if chosen is None:
                return False
            with self._mu:
                if meta.name(chosen) in self._assumed:
                    return False   # another Binding of this wave took it
                self._assumed[meta.name(chosen)] = \
                    f"{meta.namespace(claim)}/{meta.name(claim)}"
            PersistentVolumeController.bind(self.client, chosen, claim)
        return True
