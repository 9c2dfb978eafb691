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
preempt again."""

from __future__ import annotations

import functools
import os
import time
from typing import Callable, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp

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
        # zero-victim prompt retries already granted, per pod key: the
        # FIRST "candidate with zero victims" is almost always burst/wave
        # staleness (state changed under the what-if) and retries promptly;
        # a REPEAT is a real host/device filter discrepancy and must take
        # the backoff + FailedScheduling path, or it would hot-loop at wave
        # frequency invisibly
        self._zero_victim_retries: dict = {}

    def _pdb_blocked(self, scheduler, snap: Snapshot):
        import numpy as np

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
        against the same snapshot, then commit host-side in batch order. Returns the keys that preempted (victims
        evicted, pod nominated + requeued); the caller requeues the rest as
        plain unschedulable.

        Commit semantics vs the old per-pod loop (which re-snapshotted
        between pods): lanes are evaluated against the PRE-burst state, so
        two lanes can name the same victim. The commit evicts each victim
        once; a lane none of whose victims remain evictable is NOT counted
        as preempting — its space was already freed by an earlier lane and
        the ordinary retry (the eviction's move event) will place it."""
        import numpy as np

        from ..ops.lattice import default_engine_config
        from .cycle import UNSCHEDULABLE_TAINT_KEY

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
        pend_nnr = np.asarray(jax.device_get(snap.pending.node_name_req))

        # Lanes are evaluated against the PRE-burst snapshot, so preemptors
        # that agree on (class, nodeName pin, priority) get the identical
        # what-if: evaluate each distinct one once and share the answer. A
        # backlog's unschedulable tail is thousands of replicas of a few
        # templates — on the chip one 8-lane dispatch at the 5k×50k shape
        # takes 0.7–1.9 s, so a lane per pod there is an hour of what-ifs.
        lane_of = [(int(pend_cls[row]), int(pend_nnr[row]), int(pod.priority))
                   for pod, _attempts, row in eligible]
        distinct = list(dict.fromkeys(lane_of))
        # lane → (node index, victim pod keys, PDB violations)
        verdict: dict = {}
        supervisor = getattr(scheduler, "supervisor", None)
        B = PREEMPT_BURST
        for lo in range(0, len(distinct), B):
            chunk = distinct[lo: lo + B]
            pad = chunk + [chunk[-1]] * (B - len(chunk))
            cls_b = jnp.asarray([c for c, _, _ in pad], jnp.int32)
            nnr_b = jnp.asarray([n for _, n, _ in pad], jnp.int32)
            prio_b = jnp.asarray(np.array([p for _, _, p in pad], np.int32))

            def _readback(res: PreemptResult):
                return (np.asarray(jax.device_get(res.node)),
                        np.asarray(jax.device_get(res.victims)),
                        np.asarray(jax.device_get(res.n_pdb_violations)))

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
                    nodes_b, victims_b, npdb_b = supervisor.run(
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
                nodes_b, victims_b, npdb_b = _primary()
            if tr is not None:
                tr.child("what-if", time.perf_counter() - tw0)
            for i, lane in enumerate(chunk):
                verdict[lane] = (
                    int(nodes_b[i]),
                    [snap.existing_keys[e] for e in np.flatnonzero(
                        victims_b[i][: len(snap.existing_keys)])],
                    int(npdb_b[i]))

        # ---- host commit, in batch order ---- #
        handled: Set[str] = set()
        retry_soon: Set[str] = set()  # candidates whose space another lane
                                      # freed this burst: retry promptly
        for (pod, attempts, _row), lane in zip(eligible, lane_of):
            if lane not in verdict:
                continue
            node_idx, victim_keys, n_pdb = verdict[lane]
            if node_idx < 0:
                continue
            if not victim_keys:
                # a candidate with zero victims: the pod should simply
                # fit. Once per pod that is burst staleness (an earlier
                # lane/wave freed the space after the what-if's
                # snapshot) — retry promptly. A repeat means a real
                # host/device filter discrepancy: evicting nothing and
                # nominating would only mask it, so it takes the
                # normal backoff + FailedScheduling path.
                if self._zero_victim_retries.get(pod.key, 0) < 1:
                    if len(self._zero_victim_retries) > 4096:
                        # bound the ledger by dropping the OLDEST half
                        # (dict preserves insertion order) — clearing
                        # wholesale would forget the pod just recorded
                        # and re-arm the hot loop this cap prevents
                        for k in list(self._zero_victim_retries)[:2048]:
                            del self._zero_victim_retries[k]
                    self._zero_victim_retries[pod.key] = 1
                    retry_soon.add(pod.key)
                continue
            evicted_any = False
            for vk in victim_keys:
                if self.evictor.evict(scheduler, vk):
                    evicted_any = True
                    PREEMPTION_VICTIMS.inc()
            if not evicted_any:
                # every victim was already evicted for an earlier lane:
                # that lane's commit freed this space — the pod is
                # expected to fit next wave; exponential backoff here
                # would serialize the whole burst at seconds per round
                retry_soon.add(pod.key)
                continue
            self.last_pdb_violations = n_pdb
            scheduler.queue.add_nominated(pod.key,
                                          snap.node_order[node_idx])
            handled.add(pod.key)
            self._zero_victim_retries.pop(pod.key, None)
            self.successes += 1

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
