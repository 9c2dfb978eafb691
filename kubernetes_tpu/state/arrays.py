"""Device array schemas: the flat tensors the scheduler's hot path runs on.

TPU-first design decision — **pod equivalence classes**. The reference evaluates
every predicate per (pod, node) pair (generic_scheduler.go:537 ParallelizeUntil
over nodes, inside a loop over pods). But pods created by one controller share
an identical scheduling spec (requests, selectors, affinity, tolerations…); only
identity (name, creationTimestamp) differs. We intern the full scheduling spec
into a *class* (template) and evaluate the static Filter/Score lattice once per
(class, node) — [SC, N] — then fan out to pods by gather. Dynamic state
(resources used, affinity/spread counts) is re-checked per pod inside the
assignment scan against O(N)-sized rows. Worst case (all pods distinct) this
degrades gracefully to the reference's [P, N] shape; typical case it is orders
of magnitude smaller.

Schema mirrors (citations into the reference):
  * NodeArrays        ⇔ nodeinfo.NodeInfo (pkg/scheduler/nodeinfo/node_info.go:43-151)
  * ReqTable          ⇔ Resource vectors (node_info.go:143-151)
  * NodeTermTable     ⇔ NodeSelectorTerm (api core v1 types.go:2524-2556)
  * TolSetTable       ⇔ []Toleration (types.go:2789-2821)
  * PortSetTable      ⇔ HostPortInfo (node_info.go host-port accounting)
  * TermTable         ⇔ PodAffinityTerm / spread selectors (types.go:2620;
                        predicates/metadata.go:60-62 topologyPairsMaps)
  * PodClassTable     ⇔ the pod spec quotient described above
  * PodArrays         ⇔ per-pod identity + class reference

All ids are int32, -1 = absent; bitsets are uint32 words. NamedTuples are
pytrees and thread through jit/scan/shard_map unchanged.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

Array = jnp.ndarray


class NodeArrays(NamedTuple):
    valid: Array          # [N] bool
    name_id: Array        # [N] i32 node-name vocab id
    alloc: Array          # [N, R] i32 allocatable (milliCPU, KiB, KiB, pods, scalars…)
    used: Array           # [N, R] i32 requested by existing+assumed pods
    label_keys: Array     # [N, L] i32, -1 pad
    label_vals: Array     # [N, L] i32
    label_ints: Array     # [N, L] i32 parsed int value (INT_SENTINEL if not numeric)
    unschedulable: Array  # [N] bool
    taint_keys: Array     # [N, TT] i32, -1 pad
    taint_vals: Array     # [N, TT] i32
    taint_effects: Array  # [N, TT] i32 (TaintEffect), -1 pad
    topo: Array           # [N, K] i32 label-value id per topo key, -1 absent
    domain: Array         # [N, K] i32 compact per-key domain index, -1 absent
    port_pair_any: Array  # [N, PWp] u32 — (proto,port) used by any pod (any IP)
    port_pair_wild: Array # [N, PWp] u32 — (proto,port) used with wildcard IP
    port_triple: Array    # [N, PWt] u32 — (proto,port,ip) exact triples in use
    img_words: Array      # [N, IW] u32 — image-presence bitset (ImageLocality)
    vol_any: Array        # [N, VW] u32 — volumes attached by pods on the node
    vol_rw: Array         # [N, VW] u32 — volumes attached read-write
    vol_limit: Array      # [N, DR] i32 — per-driver attach limits, -1 unlimited
    vol_cnt: Array        # [N, DR] i32 — attached volumes no second pod
                          # references (Encoder.vol_owner), per driver
    avoid: Array          # [N] bool — preferAvoidPods annotation present
                          # (NodePreferAvoidPods score, node_prefer_avoid_pods.go)


class ReqTable(NamedTuple):
    """Distinct request vectors."""

    vec: Array  # [SR, R] i32


class LabelSetTable(NamedTuple):
    """Distinct pod label sets (the 'matched-by-selectors' side)."""

    keys: Array  # [SL, PL] i32, -1 pad
    vals: Array  # [SL, PL] i32


class NodeTermTable(NamedTuple):
    """Distinct node-selector terms (node-affinity terms and spec.nodeSelector
    lowered to an AND-of-IN term)."""

    valid: Array    # [SN] bool
    keys: Array     # [SN, Q] i32, -1 pad
    ops: Array      # [SN, Q] i32 (Op)
    vals: Array     # [SN, Q, V] i32, -1 pad
    ints: Array     # [SN, Q] i32 rhs for Gt/Lt
    fields: Array   # [SN, F] i32 metadata.name ids, -1 pad
    nfields: Array  # [SN] i32 count of matchFields values


class TolSetTable(NamedTuple):
    """Distinct toleration sets."""

    valid: Array    # [STL, TL] bool
    keys: Array     # [STL, TL] i32, -1 = empty key (match all)
    ops: Array      # [STL, TL] i32 (TolerationOp)
    vals: Array     # [STL, TL] i32, -1 = empty value
    effects: Array  # [STL, TL] i32, -1 = all effects


class PortSetTable(NamedTuple):
    """Distinct host-port sets, plus precomputed bitset word-masks for O(words)
    conflict checks and scan-time node updates."""

    pair: Array        # [SPP, PP] i32 pair id, -1 pad
    triple: Array      # [SPP, PP] i32 triple id, -1 pad
    wild: Array        # [SPP, PP] bool
    pair_words: Array  # [SPP, PWp] u32 — union of pair bits
    wild_words: Array  # [SPP, PWp] u32 — union of wildcard pair bits
    trip_words: Array  # [SPP, PWt] u32 — union of triple bits


class VolSetTable(NamedTuple):
    """Distinct attachable-volume sets (NoDiskConflict + max-volume-count;
    predicates.go:156-221, csi_volume_predicate.go:89). Bitsets are over the
    volume vocab; per-driver occupancy is DERIVED from bitsets by popcount
    against `ClusterTables.drv_masks`, so the engines carry only two [N, VW]
    words per node. Only SHARED volumes (two pods seen naming one) are in the
    vocab; a volume of one pod alone is a count per driver
    (`PodClassTable.vol_priv`, `NodeArrays.vol_cnt`), so a claim a pod neither
    splits a class nor widens VW."""

    any_words: Array  # [SV, VW] u32 — all volumes in the set
    rw_words: Array   # [SV, VW] u32 — volumes mounted read-write


class TermTable(NamedTuple):
    """Interned pod-affinity / anti-affinity / topology-spread terms:
    (label selector, concrete namespace set, topology key)."""

    valid: Array      # [S] bool
    req_keys: Array   # [S, Q] i32, -1 pad
    req_ops: Array    # [S, Q] i32 (Op; label-selector subset)
    req_vals: Array   # [S, Q, V] i32, -1 pad
    ns_words: Array   # [S, NW] u32 namespace bitset
    topo_key: Array   # [S] i32 topo-key index, -1 if unused


class PodClassTable(NamedTuple):
    """The pod-spec template: one row per distinct scheduling spec."""

    valid: Array        # [SC] bool
    ns: Array           # [SC] i32 namespace id (part of the class key)
    rid: Array          # [SC] i32 → ReqTable
    labelset: Array     # [SC] i32 → LabelSetTable
    nsel_term: Array    # [SC] i32 → NodeTermTable (spec.nodeSelector), -1 none
    aff_active: Array   # [SC] bool — node-affinity required present
    nterm_ids: Array    # [SC, T] i32 → NodeTermTable, -1 pad (OR of terms)
    pterm_ids: Array    # [SC, PT] i32 → NodeTermTable, -1 pad (preferred)
    pterm_w: Array      # [SC, PT] i32 weights 1-100
    tolset: Array       # [SC] i32 → TolSetTable
    portset: Array      # [SC] i32 → PortSetTable, -1 = no ports
    aff_terms: Array    # [SC, AT] i32 → TermTable, -1 pad
    anti_terms: Array   # [SC, AN] i32 → TermTable
    paff_terms: Array   # [SC, PAT] i32 → TermTable
    paff_w: Array       # [SC, PAT] i32
    panti_terms: Array  # [SC, PAN] i32 → TermTable
    panti_w: Array      # [SC, PAN] i32
    tsc_term: Array     # [SC, TS] i32 → TermTable, -1 pad
    tsc_key: Array      # [SC, TS] i32 topo-key index
    tsc_maxskew: Array  # [SC, TS] i32
    tsc_hard: Array     # [SC, TS] bool (DoNotSchedule)
    volset: Array       # [SC] i32 → VolSetTable, -1 = no SHARED volumes
    vol_priv: Array     # [SC, DR] i32 — volumes of the pod alone, per driver
    ssel_terms: Array   # [SC, SS] i32 → TermTable (SelectorSpread owners), -1 pad
    img_ids: Array      # [SC, CI] i32 → image vocab (ImageLocality), -1 pad
    lim_rid: Array      # [SC] i32 → ReqTable (container limits), -1 none


class PodArrays(NamedTuple):
    """Per-pod identity; everything spec-like lives in the class."""

    valid: Array         # [P] bool
    name_id: Array       # [P] i32
    ns: Array            # [P] i32
    cls: Array           # [P] i32 → PodClassTable
    priority: Array      # [P] i32
    creation: Array      # [P] i32 creation ordering index
    node_id: Array       # [P] i32 bound/assumed node index, -1 unbound
    node_name_req: Array # [P] i32 spec.nodeName as name id, -1 none
    pin: Array           # [P] i32 the one node the pod's required node
                         # affinity names by metadata.name on every term
                         # (state/encode.py pin_name), as name id; -1 none


class ImageTable(NamedTuple):
    """Interned container images: size in KiB per image id (ImageLocality;
    nodeinfo ImageStateSummary.Size analog — NumNodes is derived on device
    from NodeArrays.img_words so it stays patch-friendly)."""

    size_kib: Array  # [IMG] i32


class ClusterTables(NamedTuple):
    """Everything static-per-cycle bundled for the jitted lattice fns."""

    nodes: NodeArrays
    reqs: ReqTable
    labelsets: LabelSetTable
    nterms: NodeTermTable
    tolsets: TolSetTable
    portsets: PortSetTable
    terms: TermTable
    classes: PodClassTable
    images: ImageTable
    zone_keys: Array  # [2] i32 topo-key ids (modern, legacy zone label), -1 absent
    volsets: VolSetTable
    drv_masks: Array  # [DR, VW] u32 — which volume-vocab bits belong to driver d
