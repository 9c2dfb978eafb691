"""Host-side preemption driver: wires the device what-if (ops/preempt.py) into
the scheduling wave.

Flow mirrors scheduler.go:453-523 + core Preempt (generic_scheduler.go:325):
a pod that failed Filter everywhere triggers one preemption dispatch; if a
candidate node exists, the victims are evicted (async API deletes in the
reference — here a pluggable evictor), the preemptor is *nominated* onto the
node (queue bookkeeping, scheduling_queue.go:136-138) and requeued; the actual
placement happens in a later wave once the victims' resources are released.

PodEligibleToPreemptOthers (generic_scheduler.go:1085): a pod that already has
a nominated node is assumed to be waiting for its victims to exit and does not
preempt again.

One pass serves every replica of a template: the what-if returns, per lane,
the candidate nodes in pickOneNodeForPreemption's order and the reprieve
scan's victims on every one of them, and the host hands the lane's k-th
pending replica the k-th node of that order not yet handed out (a node goes
out at most once a pass, across all lanes). The nomination is published
(`status.nominatedNodeName`, through the evictor's client) before the victims
are deleted, upstream's order (scheduler.go preempt: SetNominatedNodeName,
then the DeletePod calls). Where this is upstream's own sequence and where it
is not: docs/PARITY.md "Preemption"."""

from __future__ import annotations

import functools
import os
import time
from typing import Callable, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..api.types import Pod
from ..component import trace
from ..ops.preempt import PreemptResult, preempt_batch
from ..state.cache import Snapshot
from .metrics import PREEMPTION_ATTEMPTS, PREEMPTION_VICTIMS

# preemptor lanes per fused dispatch: bursts larger than this chunk. ONE
# fixed size keeps the compile-signature count at one per Dims bucket (and
# lets the prewarmer compile it ahead of the first storm); unused lanes are
# padded with the last real preemptor and their results discarded.
PREEMPT_BURST = int(os.environ.get("KTPU_PREEMPT_BURST", "8"))


@functools.partial(jax.jit, static_argnums=(5,))
def _preempt(tables, cyc_existing, cls, nnr, prio, D, keys, pdb_blocked,
             hard_weight, ecfg):
    """One fused dispatch for a [B] burst of preemptors: build the cycle
    lattice ONCE, evaluate every lane's five-criteria what-if in parallel
    (ops/preempt.py preempt_batch). Prewarmable: sched/prewarm.py
    abstract_preempt_args mirrors this signature."""
    from ..ops.lattice import build_cycle

    uk, ev = keys
    existing = cyc_existing
    # the what-if must apply the SAME plugin composition as the live path —
    # a filter the config disabled must not block preemption candidates
    cyc = build_cycle(tables, existing, uk, ev, D, hard_weight, ecfg)
    return preempt_batch(tables, cyc, existing, cls, nnr, prio, D,
                         pdb_blocked)


class CacheEvictor:
    """Default evictor: delete the victim from the scheduler's world (the
    reference issues pod DELETE API calls, generic_scheduler.go:352-364; with
    an apiserver attached use an API-backed evictor instead)."""

    def __init__(self) -> None:
        self.evicted: List[str] = []

    def evict(self, scheduler, victim_key: str) -> bool:
        pod = scheduler.cache.get_pod(victim_key)
        if pod is None:
            return False
        scheduler.cache.remove_pod(victim_key)
        self.evicted.append(victim_key)
        return True

    def nominate(self, scheduler, pod: Pod, node_name: str) -> bool:
        """Publish the preemptor's nomination before its victims go. With no
        API there is nowhere to publish it: the queue's map is the record."""
        return True


class APIEvictor(CacheEvictor):
    """Live-cluster evictor: DELETE the victim through the API (the
    reference's generic_scheduler.go:352-364 pod deletes), then drop it
    from the cache optimistically — the informer's delete event is the
    authoritative confirmation. A victim that is already gone counts as
    evicted; any other API failure leaves the cache untouched so the
    what-if's arithmetic never diverges from the real world."""

    def __init__(self, client) -> None:
        super().__init__()
        self.client = client

    def evict(self, scheduler, victim_key: str) -> bool:
        from ..machinery import errors

        pod = scheduler.cache.get_pod(victim_key)
        if pod is None:
            return False
        ns, _, name = victim_key.partition("/")
        try:
            self.client.pods.delete(name, ns)
        except errors.StatusError as e:
            if not errors.is_not_found(e):
                return False
        scheduler.cache.remove_pod(victim_key)
        self.evicted.append(victim_key)
        return True

    def nominate(self, scheduler, pod: Pod, node_name: str) -> bool:
        """`status.nominatedNodeName` through the API (the reference's
        podPreemptor.SetNominatedNodeName -> UpdateStatus): what `kubectl
        describe`, the cluster autoscaler and a scheduler that takes over
        read. A pod that is gone, or a write the API refuses, preempts
        nothing (scheduler.go preempt returns before any delete)."""
        from ..machinery import errors

        try:
            self.client.pods.patch_status(
                pod.name, {"status": {"nominatedNodeName": node_name}},
                pod.namespace)
        except errors.StatusError:
            return False
        return True


class _Lane:
    """One lane's verdict as the hand-out reads it: the candidate nodes in
    pickOneNodeForPreemption's order, a cursor into them, and the reprieve
    scan's victims (rows of the existing-pod axis) grouped by node."""

    __slots__ = ("order", "n_cand", "bulk", "at", "served", "_rows", "_on")

    def __init__(self, order, n_cand: int, victims, bulk: bool, node_of_e):
        self.order, self.n_cand, self.bulk = order, n_cand, bulk
        self.at = self.served = 0
        rows = np.flatnonzero(victims[: node_of_e.shape[0]])
        by_node = np.argsort(node_of_e[rows], kind="stable")
        self._rows = rows[by_node]
        self._on = node_of_e[self._rows]

    def next_node(self, taken: Set[int]) -> int:
        """The best candidate not handed out yet this pass, -1 when none."""
        while self.at < self.n_cand:
            n = int(self.order[self.at])
            self.at += 1
            if n not in taken:
                return n
        return -1

    def victims_on(self, n: int):
        lo, hi = np.searchsorted(self._on, (n, n + 1))
        return self._rows[lo:hi]


class Preemptor:
    def __init__(self, evictor: Optional[CacheEvictor] = None,
                 pdb_source: Optional[Callable[[], list]] = None) -> None:
        self.evictor = evictor or CacheEvictor()
        # pdb_source() → iterable of (namespace, LabelSelector,
        # disruptions_allowed) triples — the PDB lister the reference hands to
        # genericScheduler (factory.go wires a policy lister). Victims whose
        # eviction would violate a PDB (allowed ≤ 0) become the what-if's
        # pdb_blocked bits (filterPodsWithPDBViolation semantics).
        self.pdb_source = pdb_source
        self.attempts = 0
        self.successes = 0
        self.last_pdb_violations = 0
        # what the last pass did, as the wave's record carries it
        # (docs/OBSERVABILITY.md `preempt_*`); empty when none ran
        self.last_pass: dict = {}
        # zero-victim prompt retries already granted, per pod key: the
        # FIRST "candidate with zero victims" is almost always burst/wave
        # staleness (state changed under the what-if) and retries promptly;
        # a REPEAT is a real host/device filter discrepancy and must take
        # the backoff + FailedScheduling path, or it would hot-loop at wave
        # frequency invisibly
        self._zero_victim_retries: dict = {}

    def _pdb_blocked(self, scheduler, snap: Snapshot):
        E = len(snap.existing_keys)
        blocked = np.zeros((max(E, 1),), bool)
        if self.pdb_source is None:
            return blocked
        from ..api.semantics import selector_matches

        # reference-faithful matching (generic_scheduler.go:1080-1098):
        # a nil/EMPTY selector matches NOTHING, and unlabeled pods are
        # skipped ("A pod with no labels will not match any PDB")
        pdbs = [(ns, sel, allowed) for ns, sel, allowed in self.pdb_source()
                if allowed <= 0 and sel is not None
                and getattr(sel, "requirements", ())]
        if not pdbs:
            return blocked
        for i, key in enumerate(snap.existing_keys):
            if not key:
                continue
            pod = scheduler.cache.get_pod(key)
            if pod is None or not pod.labels:
                continue
            for ns, sel, _ in pdbs:
                if ns == pod.namespace and selector_matches(sel, pod.labels):
                    blocked[i] = True
                    break
        return blocked

    def try_preempt(self, scheduler, pod: Pod, attempts: int,
                    snap: Snapshot, now: float) -> bool:
        """Single-preemptor convenience (extender path, tests): a burst of
        one. Returns True iff preemption was performed (victims evicted and
        the pod nominated + requeued)."""
        return pod.key in self.preempt_burst(
            scheduler, [(pod, attempts)], snap, now)

    def preempt_burst(self, scheduler, burst: Sequence[Tuple[Pod, int]],
                      snap: Snapshot, now: float) -> Set[str]:
        """The whole wave's preemption pass as ONE fused device dispatch
        (chunked at PREEMPT_BURST lanes, one lane per DISTINCT preemptor
        template): evaluate every unschedulable priority pod's what-if
        against the same snapshot, then hand the nodes out host-side in
        batch order. Returns the keys that preempted (victims evicted, pod
        nominated + requeued) or were told to retry promptly; the caller
        requeues the rest as plain unschedulable.

        The hand-out: a lane's k-th pending replica takes the k-th node of
        the lane's order that no earlier pod of the pass took, with the
        victims the reprieve scan left on that node. A lane hands out no
        more nodes than it has replicas pending; replicas beyond its
        candidates are unschedulable. A lane whose class meets its own
        replicas through required (anti-)affinity or a hard spread
        constraint (`bulk` false) takes ONE node a pass — its what-if did
        not see the first replica where it was sent — and its other
        replicas retry promptly. Then every nomination is published, then
        every victim evicted; what the pass did is `last_pass` (the wave's
        record carries it)."""
        from ..ops.lattice import default_engine_config
        from .cycle import UNSCHEDULABLE_TAINT_KEY

        self.last_pass = {}
        # ---- host-side eligibility (PodEligibleToPreemptOthers) ---- #
        row_of = {k: i for i, (k, _) in enumerate(snap.pending_keys)}
        eligible: List[Tuple[Pod, int, int]] = []  # (pod, attempts, row)
        for pod, attempts in burst:
            if pod.priority <= 0:
                continue  # only priority pods preempt
            if scheduler.queue.nominated_node(pod.key) is not None:
                # it failed even on its nominated node (someone stole the
                # freed space) — clear the nomination and re-evaluate in
                # THIS burst. The reference defers re-preemption to the
                # next failure because its victims exit asynchronously;
                # our evictors remove victims synchronously, so a
                # nominated pod failing again means the space is truly
                # gone and the what-if against the fresh snapshot is the
                # correct immediate response (parking it in backoff just
                # serializes the storm at seconds per round).
                scheduler.queue.delete_nominated(pod.key)
            row = row_of.get(pod.key)
            if row is None:
                continue
            eligible.append((pod, attempts, row))
        if not eligible:
            return set()
        self.attempts += len(eligible)
        PREEMPTION_ATTEMPTS.inc(len(eligible))

        enc = scheduler.encoder
        uk = jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY))
        ev = jnp.int32(enc.vocabs.label_vals.get(""))
        blocked = self._pdb_blocked(scheduler, snap)
        pdb_arr = np.zeros((snap.existing.valid.shape[0],), bool)
        pdb_arr[: blocked.shape[0]] = blocked
        pdb_dev = jnp.asarray(pdb_arr)
        hw = jnp.float32(getattr(scheduler, "hard_pod_affinity_weight", 1.0))
        ecfg = getattr(scheduler, "engine_config", None) \
            or default_engine_config()
        prewarmer = getattr(scheduler, "prewarmer", None)

        pend_cls = np.asarray(jax.device_get(snap.pending.cls))
        # the one node a preemptor may land on: spec.nodeName, else its pin
        # (`PodArrays.pin`: a DaemonSet pod's). Either way the what-if's
        # candidates are that node alone, and pinned replicas of one class
        # are a lane each: every one is handed ITS node, never the k-th of
        # a shared order
        pend_nnr = np.asarray(jax.device_get(snap.pending.node_name_req))
        pend_pin = np.asarray(jax.device_get(snap.pending.pin))
        pend_nnr = np.where(pend_nnr >= 0, pend_nnr, pend_pin)

        # Lanes are evaluated against the PRE-burst snapshot, so preemptors
        # that agree on (class, named node, priority) get the identical
        # what-if: evaluate each distinct one once and share the answer. A
        # backlog's unschedulable tail is thousands of replicas of a few
        # templates — on the chip one 8-lane dispatch at the 5k×50k shape
        # takes 0.7–1.9 s, so a lane per pod there is an hour of what-ifs.
        lane_of = [(int(pend_cls[row]), int(pend_nnr[row]), int(pod.priority))
                   for pod, _attempts, row in eligible]
        distinct = list(dict.fromkeys(lane_of))
        # which node each row of the existing-pod axis is on: a lane's
        # victim mask names rows, the hand-out needs them by node
        node_of_e = np.asarray(jax.device_get(snap.existing.node_id))[
            : len(snap.existing_keys)]
        verdict: dict = {}   # lane → _Lane
        dispatches = 0
        supervisor = getattr(scheduler, "supervisor", None)
        B = PREEMPT_BURST
        for lo in range(0, len(distinct), B):
            chunk = distinct[lo: lo + B]
            pad = chunk + [chunk[-1]] * (B - len(chunk))
            cls_b = jnp.asarray([c for c, _, _ in pad], jnp.int32)
            nnr_b = jnp.asarray([n for _, n, _ in pad], jnp.int32)
            prio_b = jnp.asarray(np.array([p for _, _, p in pad], np.int32))

            def _readback(res: PreemptResult):
                # per lane an [N] order and one [E] mask; never [N, E]
                return tuple(np.asarray(a) for a in jax.device_get(
                    (res.order, res.n_candidates, res.node_victims,
                     res.bulk)))

            def _primary():
                # the lookup carries the snapshot's mesh signature: a
                # mesh-sharded burst program must never be fed
                # single-device arrays (and vice versa) — see
                # sched/prewarm.py lookup isolation
                compiled = prewarmer.lookup_preempt(snap.dims, B,
                                                    mesh=snap.mesh) \
                    if prewarmer is not None else None
                if compiled is not None:
                    ok, out = prewarmer.call(
                        compiled, snap.tables, snap.existing, cls_b, nnr_b,
                        prio_b, (uk, ev), pdb_dev, hw, ecfg)
                    if ok:
                        return _readback(out)
                return _readback(_preempt(
                    snap.tables, snap.existing, cls_b, nnr_b, prio_b,
                    snap.dims.D, (uk, ev), pdb_dev, hw, ecfg))

            def _fallback(dev, hung=False):
                # the same burst, re-dispatched on the CPU backend:
                # committed inputs pin the execution there. A wedged
                # primary's buffers are untouchable — and in degraded
                # waves the snapshot is already fallback-resident (the
                # scheduler routes fresh snapshots via snapshot_device()),
                # so the only unreachable case is the backend dying
                # BETWEEN this wave's cycle and its preemption pass:
                # abort crash-consistently (nothing evicted), the pods
                # requeue, and the next wave's snapshot is safe.
                if hung:
                    raise RuntimeError(
                        "preempt fallback: primary buffers unreachable "
                        "(hung backend)")
                tb, ex, cb, nb, pb, ky, pd, hw_f, ec = jax.device_put(
                    (snap.tables, snap.existing, cls_b, nnr_b, prio_b,
                     (uk, ev), pdb_dev, hw, ecfg), dev)
                with jax.default_device(dev):
                    return _readback(_preempt(tb, ex, cb, nb, pb,
                                              snap.dims.D, ky, pd, hw_f, ec))

            # one what-if burst, dispatch and readback (the supervisor
            # runs both on its worker): a child of the traced wave's pass
            tr = trace.current()
            tw0 = time.perf_counter()
            if supervisor is not None:
                from dataclasses import replace as _dc_replace

                from ..parallel.mesh import mesh_key as _mesh_key
                from .supervisor import DispatchAbandonedError

                try:
                    order_b, ncand_b, victims_b, bulk_b = supervisor.run(
                        "preempt",
                        (_dc_replace(snap.dims, has_node_name=False, P=1), B,
                         _mesh_key(snap.mesh)),
                        _primary, _fallback)
                except DispatchAbandonedError:
                    # both backends refused the burst: NOTHING in this chunk
                    # (or the remaining ones) was evaluated, so nothing is
                    # evicted for them — every pod without a verdict takes
                    # the ordinary unschedulable/requeue path upstream.
                    # Crash-consistent: evictions only ever happen after a
                    # successful readback.
                    break
            else:
                order_b, ncand_b, victims_b, bulk_b = _primary()
            dispatches += 1
            if tr is not None:
                tr.child("what-if", time.perf_counter() - tw0)
            for i, lane in enumerate(chunk):
                verdict[lane] = _Lane(order_b[i], int(ncand_b[i]),
                                      victims_b[i], bool(bulk_b[i]),
                                      node_of_e)

        # ---- the hand-out, in batch order: host arithmetic only ---- #
        handed: list = []             # (pod, node index, victim rows)
        taken: Set[int] = set()       # nodes handed out this pass
        retry_soon: Set[str] = set()  # pods whose room this pass freed or
                                      # may have freed: retry promptly
        for (pod, attempts, _row), key in zip(eligible, lane_of):
            lane = verdict.get(key)
            if lane is None or not lane.n_cand:
                continue
            if lane.served and not lane.bulk:
                # one node a pass: the next wave's Filter sees the first
                # replica on its node; exponential backoff here would
                # serialize the burst at seconds per round
                retry_soon.add(pod.key)
                continue
            n = lane.next_node(taken)
            if n < 0:
                continue   # more replicas than candidate nodes
            taken.add(n)
            lane.served += 1
            rows = lane.victims_on(n)
            if rows.size:
                handed.append((pod, n, rows))
                continue
            # a candidate with zero victims: the pod should simply fit.
            # Once per pod that is burst staleness (an earlier wave freed
            # the space after the what-if's snapshot) — retry promptly. A
            # repeat means a real host/device filter discrepancy: evicting
            # nothing and nominating would only mask it, so it takes the
            # normal backoff + FailedScheduling path.
            if self._zero_victim_retries.get(pod.key, 0) < 1:
                if len(self._zero_victim_retries) > 4096:
                    # bound the ledger by dropping the OLDEST half (dict
                    # preserves insertion order) — clearing wholesale would
                    # forget the pod just recorded and re-arm the hot loop
                    # this cap prevents
                    for k in list(self._zero_victim_retries)[:2048]:
                        del self._zero_victim_retries[k]
                self._zero_victim_retries[pod.key] = 1
                retry_soon.add(pod.key)

        # ---- nominate, then evict (upstream's order), each under a span
        # the apiserver's and the store's own spans nest below ---- #
        handled: Set[str] = set()
        victims = 0
        tn0 = tn1 = tn2 = time.perf_counter()
        if handed:
            tr = trace.current()
            tok = tr.begin("nominate") if tr is not None else None
            named = [h for h in handed if self.evictor.nominate(
                scheduler, h[0], snap.node_order[h[1]])]
            tn1 = time.perf_counter()
            if tr is not None:
                tr.end(tok, tn1 - tn0)
                tok = tr.begin("evict")
            for pod, n, rows in named:
                evicted = sum(1 for e in rows if self.evictor.evict(
                    scheduler, snap.existing_keys[e]))
                if not evicted:
                    # its victims left by another hand since the snapshot:
                    # the room is there, the pod is expected to fit next
                    # wave
                    retry_soon.add(pod.key)
                    continue
                victims += evicted
                self.last_pdb_violations = int(blocked[rows].sum())
                scheduler.queue.add_nominated(pod.key, snap.node_order[n])
                handled.add(pod.key)
                self._zero_victim_retries.pop(pod.key, None)
                self.successes += 1
            PREEMPTION_VICTIMS.inc(victims)
            tn2 = time.perf_counter()
            if tr is not None:
                tr.end(tok, tn2 - tn1)
        self.last_pass = {
            "preempt_lanes": len(distinct),
            "preempt_preemptors": len(eligible),
            "preempt_dispatches": dispatches,
            "preempt_nodes_handed_out": len(taken),
            "preempt_nominated": len(handled),
            "preempt_victims": victims,
            "preempt_retry_soon": len(retry_soon),
            "preempt_nominate_s": round(tn1 - tn0, 6),
            "preempt_evict_s": round(tn2 - tn1, 6)}

        if not handled:
            # no lane evicted anything: a zero-victim candidate here is a
            # genuine filter discrepancy, not burst overlap — every pod
            # takes the ordinary unschedulable/backoff path
            return set()
        # cache changed → move event for everyone else; the nominated
        # preemptors (and the lanes whose space an earlier lane freed)
        # go straight back to activeQ, attempt counts preserved: their
        # next attempt is expected to succeed once the victims are
        # gone, and serving the accumulated exponential backoff first
        # would stall the burst for seconds per round
        # (queue.add_prompt_retry's documented deviation)
        scheduler.queue.move_all_to_active(now)
        for pod, attempts, _row in eligible:
            if pod.key in handled or pod.key in retry_soon:
                scheduler.queue.add_prompt_retry(
                    pod, attempts=attempts, now=now)
        return handled | retry_soon
