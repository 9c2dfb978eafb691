"""The TPU extender backend: the device lattice behind the extender verbs.

This is the north-star integration surface (SURVEY §north-star; build plan
step 5): a stock kube-scheduler configured with an Extender
(apis/config/legacy_types.go:194 — URLPrefix/FilterVerb/PrioritizeVerb/
PreemptVerb/BindVerb/NodeCacheCapable) POSTs ExtenderArgs JSON per pod; we
answer from the same watch-fed mirror + (pods × nodes) lattice that the
standalone scheduler uses.

Verb semantics mirrored from the reference's HTTPExtender client
(core/extender.go):
  * Filter (:289): return the feasible subset (names when nodeCacheCapable,
    full nodes otherwise) + FailedNodes reasons.
  * Prioritize (:355): HostPriorityList with scores 0..MaxExtenderPriority=10;
    the caller rescales ×weight×(100/10) (generic_scheduler.go:868).
  * ProcessPreemption (:166): given candidate victim sets, re-verify each
    node's viability with our own predicates and return the surviving subset
    (possibly shrunk per node).
  * Bind (:397): commit the placement through our binder (apiserver write).

The backend is also 'cache capable' in the reference sense (extender.go:454
IsInterested / managedResources): `interested()` lets deployments scope us to
pods carrying a managed resource.

The mirror's feed is `observe_pod` / `forget_pod` / `observe_node` /
`forget_node`, one informer event each (extender/served.py wires them);
`sync_*` are whole-set reconciles for tests. `bind` with a binder assumes
the pod in the cache before it writes and the informer's echo confirms it
(state/cache.py assume / finish / expire), so the next pod's `filter` sees
the placement whether or not the echo has arrived.

One evaluation a pod: `filter` takes ONE snapshot and runs ONE program
(sched/cycle.py `_evaluate`: mask, failure components, scores) and keeps the
result on the host for the pod's UID; its own answer and the same pod's
`prioritize` answer are both cut from those arrays. The kept evaluation is
good for as long as the mirror's epoch (this backend's count of changes to
what the lattice reads) has not moved; whatever else arrives evaluates
afresh the same way.

One flight-recorder record per POD (docs/OBSERVABILITY.md): the `filter` ->
`prioritize` -> `bind` of one UID, in the wave record's shape, closed after
the `bind`'s answer (`answered`) or by the next pod's first verb.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from itertools import compress
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..api.types import Node, Pod
from ..api.v1 import node_from_v1, pod_from_v1
from ..component import trace
from ..component.metrics import DEFAULT_REGISTRY as REG
from ..sched.cycle import _evaluate, snapshot_with_keys
from ..sched.telemetry import SchedulerTelemetry, xla_scope
from ..state.cache import SchedulerCache
from ..state.dims import Dims
from ..state.encode import Encoder
from .wire import (
    ExtenderArgs,
    ExtenderBindingArgs,
    ExtenderBindingResult,
    ExtenderFilterResult,
    ExtenderPreemptionArgs,
    ExtenderPreemptionResult,
    HostPriorityList,
    MAX_EXTENDER_PRIORITY,
    MetaVictims,
)

# reference predicate failure reason strings (algorithm/predicates/error.go),
# keyed by MaskComponents field order
_REASONS = (
    "node(s) didn't match node selector",
    "node(s) had taints that the pod didn't tolerate",
    "Insufficient resources",
    "node(s) didn't have free ports for the requested pod ports",
    "node(s) didn't match pod affinity rules",
    "node(s) didn't match pod anti-affinity rules",
    "node(s) didn't match pod topology spread constraints",
    "node(s) didn't match the requested hostname",
    "node(s) had volume conflicts or exceeded volume limits",
)

EXTENDER_EVALUATIONS = REG.counter(
    "extender_evaluations_total",
    "Verb answers by where their arrays came from: `computed` by one "
    "snapshot and one program, `reused` from the evaluation kept for the "
    "pod's UID since its filter",
    labels=("result",))

_UNKNOWN = -1   # the reason code of a candidate the mirror does not hold


@functools.lru_cache(maxsize=None)   # at most 2 ** len(_REASONS) + 1 texts
def _reason_text(code: int) -> str:
    """A failed node's FailedNodes text from its reason code (bit j set
    where MaskComponents field j refuses)."""
    if code == _UNKNOWN:
        return "node not found in extender cache"
    return "; ".join(r for j, r in enumerate(_REASONS) if code >> j & 1) \
        or "node is not feasible"


class _Evaluation:
    """One pod evaluated against the mirror, on the host: what `filter`'s
    and `prioritize`'s answers are cut from. `mask`, `codes` and `scores`
    are [N] rows indexed by the slot of a name in `order`."""

    __slots__ = ("uid", "epoch", "order", "mask", "codes", "scores")

    def __init__(self, uid: str, epoch: int, order: List[str],
                 mask: np.ndarray, codes: np.ndarray,
                 scores: np.ndarray) -> None:
        self.uid, self.epoch, self.order = uid, epoch, order
        self.mask, self.codes, self.scores = mask, codes, scores


class _PodStats:
    """What `SchedulerTelemetry.finish_wave` reads of a wave's stats, for the
    one pod a record is about."""

    __slots__ = ("attempted", "scheduled", "unschedulable", "bind_errors",
                 "aborted")

    def __init__(self, scheduled: int, unschedulable: int,
                 bind_errors: int) -> None:
        self.attempted = 1
        self.scheduled = scheduled
        self.unschedulable = unschedulable
        self.bind_errors = bind_errors
        self.aborted = 0


class _PodRecord:
    """One pod's passage through the verbs: the span its phases are marked
    on (its Trace is `trace.current()` while a verb of this pod runs, so
    the cache, the binder, the in-process apiserver and the store file
    their time below the phase that called them) and what is counted
    beside it."""

    __slots__ = ("uid", "key", "pod", "span", "verbs", "dispatches",
                 "snapshots", "evaluations", "eval_reused", "split",
                 "assumed_outstanding", "confirm", "feasible", "bound",
                 "bind_error", "dims", "mode")

    def __init__(self, uid: str, key: str, span) -> None:
        self.uid, self.key, self.span = uid, key, span
        self.pod: Optional[Pod] = None
        self.verbs: List[str] = []
        self.dispatches = self.snapshots = 0
        self.evaluations = self.eval_reused = 0
        self.split = [0.0, 0.0, 0.0]   # launch, execute, readback seconds
        self.assumed_outstanding: Optional[int] = None
        self.confirm: Optional[List[float]] = None
        self.feasible: Optional[int] = None
        self.bound = self.bind_error = False
        self.dims = None
        self.mode = ""


class ExtenderBackend:
    """Watch-fed mirror + lattice evaluation for one extender deployment."""

    def __init__(
        self,
        cache: Optional[SchedulerCache] = None,
        base_dims: Optional[Dims] = None,
        managed_resources: Sequence[str] = (),
        binder: Optional[Callable[[Pod, str], bool]] = None,
        pod_lookup: Optional[Callable[[str, str], Optional[Pod]]] = None,
    ) -> None:
        self.cache = cache or SchedulerCache()
        self.encoder = Encoder()
        self.base_dims = base_dims
        self.managed_resources = frozenset(managed_resources)
        self.binder = binder
        # (namespace, name) -> the pending Pod, for a `bind` whose pod this
        # backend never filtered (the served extender: its pod informer)
        self.pod_lookup = pod_lookup
        # KTPU_TELEMETRY=0 turns the per-pod record off like the rest
        self.telemetry = SchedulerTelemetry(name="extender")
        # () -> what the watch plane did since the last call, onto the pod's
        # record (the served extender: its informers' relists, the store's
        # counters), as Scheduler.watch_plane is
        self.watch_plane: Optional[Callable[[], dict]] = None
        self._mu = threading.Lock()
        # the mirror's epoch: every change to what the lattice reads is made
        # and counted under `_mirror_mu` (the informers' threads do not hold
        # `_mu`), so an evaluation whose epoch still stands saw all of them
        self._mirror_mu = threading.Lock()
        self._epoch = 0
        self._kept: Optional[_Evaluation] = None
        # {name: slot} of the last node order a candidate list was looked
        # up in, rebuilt when the order changes
        self._slot_order: List[str] = []
        self._slot_of: Dict[str, int] = {}
        self._rec: Optional[_PodRecord] = None
        self._arrival = threading.local()
        self.bound: List[Tuple[str, str]] = []  # (pod key, node) — audit trail

    # ------------------------------------------------------------------ #
    # mirror feed: one informer event each (extender/served.py), and the
    # whole-set reconciles tests call by hand
    # ------------------------------------------------------------------ #

    def observe_pod(self, pod: Pod, live: bool = True) -> None:
        """A pod informer's add or update. A pod on a node is mirrored (an
        assumed one confirmed: the echo of this backend's own Binding);
        one that has terminated or is being deleted (`live` false) frees
        its node; a pending pod is the caller's, not the mirror's."""
        cache = self.cache
        if not pod.node_name:
            return
        with self._mirror_mu:
            held = cache.get_pod(pod.key)
            if not live:
                if held is None:
                    return
                cache.remove_pod(pod.key)
            elif held is not None and not cache.is_assumed(pod.key):
                cache.update_pod(pod)
            else:
                cache.add_pod(pod)
                if held is not None and held.node_name == pod.node_name:
                    # the echo of this backend's own Binding: the pod has
                    # been counted on that node since `bind` assumed it,
                    # and a Binding changes `nodeName` and a condition, so
                    # no row the lattice reads has changed. It lands during
                    # or just after the NEXT pod's `filter`: counting it
                    # would cost that pod its kept evaluation
                    return
            self._epoch += 1

    def forget_pod(self, key: str) -> None:
        """A pod informer's delete."""
        with self._mirror_mu:
            if self.cache.get_pod(key) is not None:
                self.cache.remove_pod(key)
                self._epoch += 1

    def observe_node(self, node: Node) -> None:
        with self._mirror_mu:
            if self.cache.get_node(node.name) is None:
                self.cache.add_node(node)
            else:
                self.cache.update_node(node)
            self._epoch += 1

    def forget_node(self, name: str) -> None:
        with self._mirror_mu:
            if self.cache.get_node(name) is not None:
                self.cache.remove_node(name)
                self._epoch += 1

    def sync_nodes(self, nodes: Sequence[Node]) -> None:
        """Full reconcile: `nodes` is the complete node set (informer relist)."""
        known = {n.name for n in self.cache.nodes()}
        self.upsert_nodes(nodes)
        for gone in known - {n.name for n in nodes}:
            self.forget_node(gone)

    def upsert_nodes(self, nodes: Sequence[Node]) -> None:
        """Partial refresh: update/insert only — used for the node objects
        riding a non-cache-capable ExtenderArgs, which carry just the subset
        that survived the caller's earlier predicates for one pod and must NOT
        prune the rest of the mirror."""
        for n in nodes:
            self.observe_node(n)

    def sync_scheduled_pods(self, pods: Sequence[Pod]) -> None:
        with self._mirror_mu:
            known = {p.key for p in self.cache.scheduled_pods()}
            incoming = set()
            for p in pods:
                if not p.node_name:
                    continue
                incoming.add(p.key)
                if p.key in known:
                    self.cache.update_pod(p)
                else:
                    self.cache.add_pod(p)
            for gone in known - incoming:
                self.cache.remove_pod(gone)
            self._epoch += 1

    # ------------------------------------------------------------------ #
    # IsInterested (extender.go:454-470)
    # ------------------------------------------------------------------ #

    def interested(self, pod: Pod) -> bool:
        if not self.managed_resources:
            return True
        for name, _ in pod.requests.scalars:
            if name in self.managed_resources:
                return True
        return False

    # ------------------------------------------------------------------ #
    # the per-pod record (callers hold self._mu)
    # ------------------------------------------------------------------ #

    def arrived(self, t: float) -> None:
        """The server read the clock when this thread's request arrived,
        before it knew the verb or the pod: the verb it then calls starts
        its `decode` there."""
        self._arrival.t = t

    def _enter(self, uid: str, key: str, verb: str) -> Optional[_PodRecord]:
        """The record of the pod this verb is about: the open one if it is
        this pod's (the stretch since its last mark was the caller's:
        transport and the stock scheduler's own work), else a new one, the
        other pod's closed first. None with telemetry off."""
        tel = self.telemetry
        if not tel.enabled:
            return None
        t_in = getattr(self._arrival, "t", None)
        self._arrival.t = None
        rec = self._rec
        if rec is not None and rec.uid == uid:
            rec.span.trace.step("caller", at=t_in)
        else:
            if rec is not None:
                self._finish(rec)
            span = tel.wave_span("extender-pod")
            if t_in is not None:
                span.trace.start = t_in
            rec = self._rec = _PodRecord(uid, key, span)
        rec.verbs.append(verb)
        return rec

    @contextlib.contextmanager
    def _serving(self, uid: str, key: str, verb: str):
        """`_enter`, with the record's Trace this thread's `trace.current()`
        while the verb runs."""
        rec = self._enter(uid, key, verb)
        token = trace.activate(rec.span.trace) if rec else None
        tel = self.telemetry
        try:
            # what the verb compiles (a Dims bucket the compile-ahead did
            # not see) is the XLA account's under the verb, on the pod's
            # record: the stock scheduler's `httpTimeout` is waiting
            with xla_scope(f"extender/{verb}", on_path=True,
                           seq=tel.recorder.next_seq(),
                           sink=tel.note_supervisor_event):
                yield rec
        finally:
            if token is not None:
                trace.deactivate(token)

    def _finish(self, rec: _PodRecord) -> None:
        self._rec = None
        extra = {"pod": rec.key, "verbs": rec.verbs,
                 "dispatches": rec.dispatches, "snapshots": rec.snapshots,
                 "evaluations": rec.evaluations,
                 "eval_reused": rec.eval_reused, "snapshot_mode": rec.mode}
        if rec.feasible is not None:
            extra["feasible"] = rec.feasible
        if rec.assumed_outstanding is not None:
            extra["assumed_outstanding"] = rec.assumed_outstanding
            extra["waits"] = {"confirm": rec.confirm}
        if self.watch_plane is not None:
            extra.update(self.watch_plane())
        if rec.dispatches:
            self.telemetry.note_device_split(*rec.split, token=rec.span)
        self.telemetry.finish_wave(
            rec.span, engine="extender", dims=rec.dims, extra=extra,
            stats=_PodStats(int(rec.bound), int(rec.feasible == 0),
                            int(rec.bind_error)))

    def answered(self, verb: str) -> None:
        """The server has this verb's reply encoded and is about to send it:
        the stretch since the last mark (candidate loops, reasons, JSON out)
        was the `answer`; a `bind`'s answer closes the pod's record."""
        with self._mu:
            rec = self._rec
            if rec is None or not rec.verbs or rec.verbs[-1] != verb:
                return
            rec.span.mark("answer")
            if verb == "bind":
                self._finish(rec)

    def flush_record(self) -> None:
        """Close the open record, if any (shutdown; a caller that drives
        the backend without a server)."""
        with self._mu:
            if self._rec is not None:
                self._finish(self._rec)

    # ------------------------------------------------------------------ #
    # snapshot + dispatch, shared by the verbs
    # ------------------------------------------------------------------ #

    def _snapshot_for(self, pod: Pod, cache: Optional[SchedulerCache] = None,
                      rec: Optional[_PodRecord] = None):
        cache = cache or self.cache
        snap, keys = snapshot_with_keys(cache, self.encoder, [pod],
                                        self.base_dims)
        if rec is not None:
            rec.snapshots += 1
            rec.dims, rec.mode = snap.dims, cache.last_snapshot_mode
            rec.span.mark("snapshot")
        return snap, keys

    @staticmethod
    def _dispatch(snap, keys, rec: Optional[_PodRecord] = None):
        """One device call of `_evaluate` over the snapshot, read back to
        the host; on a record, its launch / execute / readback split is
        added to the pod's."""
        t0 = time.perf_counter()
        # a compile here is the enclosing scope's (the verb's, or the
        # compile-ahead's), at these capacities
        with xla_scope(None, (snap.dims,), ("dims",)):
            out = _evaluate(snap.tables, snap.pending, keys, snap.dims.D,
                            snap.existing)
        if rec is None:
            return jax.device_get(out)
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        rec.span.mark("dispatch")
        host = jax.device_get(out)
        t3 = time.perf_counter()
        rec.span.mark("readback")
        rec.dispatches += 1
        for i, dt in enumerate((t1 - t0, t2 - t1, t3 - t2)):
            rec.split[i] += dt
        return host

    def _evaluate_pod(self, pod: Pod, uid: str,
                      rec: Optional[_PodRecord]) -> _Evaluation:
        """Evaluate `pod` against the mirror as it stands: one snapshot,
        one program, and the result kept for `uid` in place of the last.
        The epoch is read BEFORE the snapshot: a change that lands during
        it moves the epoch past the kept one, whether or not the snapshot
        caught it."""
        with self._mirror_mu:
            epoch = self._epoch
        snap, keys = self._snapshot_for(pod, rec=rec)
        mask, comp, scores = self._dispatch(snap, keys, rec)
        codes = np.zeros(mask.shape[1], np.int32)
        for j, part in enumerate(comp):
            codes |= (~part[0]).astype(np.int32) << j
        if rec is not None:
            rec.evaluations += 1
        EXTENDER_EVALUATIONS.inc(result="computed")
        kept = self._kept = _Evaluation(uid, epoch, snap.node_order, mask[0],
                                        codes, scores[0])
        return kept

    @staticmethod
    def _candidates(args: ExtenderArgs) -> Optional[List[str]]:
        """The names a verb is asked about, in either form of the
        arguments; None where neither is present."""
        if args.node_names is not None:
            return args.node_names
        if args.nodes is not None:
            return [n["metadata"]["name"] for n in args.nodes]
        return None

    def _slots(self, names: Sequence[str], order: List[str]) -> np.ndarray:
        """The slot in `order` of each candidate name, -1 for a name the
        mirror does not hold. Every node in the mirror's own order is what a
        `nodeCacheCapable` scheduler sends to `filter`."""
        if names == order:
            return np.arange(len(order))
        if order != self._slot_order:
            self._slot_order = order
            self._slot_of = {name: i for i, name in enumerate(order)}
        slot = self._slot_of.get
        return np.fromiter((slot(name, _UNKNOWN) for name in names), np.intp,
                           len(names))

    def compile_ahead(self) -> list:
        """Run the verbs' program once over the mirror as it stands, and
        the cache's patch-scatter ladder, so that no later verb at these
        capacities compiles (upstream's `httpTimeout` defaults to 5 s; the
        flagship's programs compile for a minute). A real call at the live
        shapes, as `SchedulerCache.warm_patch_ladder` is: what seeds the
        cache the verbs' dispatch consults. Returns [(Dims, program)]; on a
        traced start (`trace.current()`) each call is a span under its
        name."""
        tr = trace.current()   # a start's account: each call a span on it

        def timed(name: str, call, *args):
            if tr is None:
                return call(*args)
            tok, t0 = tr.begin(name), time.perf_counter()
            try:
                return call(*args)
            finally:
                tr.end(tok, time.perf_counter() - t0)

        with self._mu, xla_scope("compile-ahead", on_path=False):
            snap, keys = timed("snapshot", self._snapshot_for, Pod(
                name="compile-ahead", namespace="kube-system"))
            timed("evaluate", self._dispatch, snap, keys)
            timed("patch-ladder", self.cache.warm_patch_ladder, snap)
            return [(snap.dims, "evaluate")]

    # ------------------------------------------------------------------ #
    # verb: Filter
    # ------------------------------------------------------------------ #

    def filter(self, args: ExtenderArgs) -> ExtenderFilterResult:
        with self._mu:
            try:
                pod = pod_from_v1(args.pod)
            except Exception as e:  # noqa: BLE001 — wire boundary
                return ExtenderFilterResult(error=f"bad pod: {e}")
            uid = pod.uid or pod.key
            with self._serving(uid, pod.key, "filter") as rec:
                return self._filter(args, pod, uid, rec)

    def _filter(self, args: ExtenderArgs, pod: Pod, uid: str,
                rec: Optional[_PodRecord]) -> ExtenderFilterResult:
        cache_capable = args.node_names is not None
        if not cache_capable and args.nodes is not None:
            # non-cache-capable callers ship full node objects; refresh the
            # mirror from them so the lattice reflects the caller's view
            self.upsert_nodes([node_from_v1(n) for n in args.nodes])
        if rec is not None:
            rec.pod = pod
            rec.span.mark("decode")
        # what the last Bindings waited for their echo, and the assumed pods
        # whose echo is still out: one whose echo never comes expires here
        confirm, outstanding = self.cache.drain_confirm_waits()
        if outstanding:
            with self._mirror_mu:
                if self.cache.cleanup(self.telemetry.clock()):
                    self._epoch += 1
        if rec is not None:
            rec.confirm, rec.assumed_outstanding = confirm, outstanding

        # always afresh: a retry of the stock scheduler sees the world as
        # it is now
        ev = self._evaluate_pod(pod, uid, rec)
        names = self._candidates(args)
        if names is None:   # neither form present: every mirrored node
            names = ev.order
        slots = self._slots(names, ev.order)
        known = slots >= 0
        ok = known & ev.mask[slots]
        passing = list(compress(names, ok.tolist()))
        refused = ~ok
        failed = dict(zip(
            compress(names, refused.tolist()),
            map(_reason_text,
                np.where(known, ev.codes[slots], _UNKNOWN)[refused].tolist())))
        if rec is not None:
            rec.feasible = len(passing)

        if cache_capable:
            return ExtenderFilterResult(node_names=passing, failed_nodes=failed)
        by_name = {n["metadata"]["name"]: n for n in (args.nodes or [])}
        return ExtenderFilterResult(
            nodes=[by_name[n] for n in passing if n in by_name],
            failed_nodes=failed,
        )

    # ------------------------------------------------------------------ #
    # verb: Prioritize
    # ------------------------------------------------------------------ #

    def prioritize(self, args: ExtenderArgs) -> HostPriorityList:
        with self._mu:
            pod = pod_from_v1(args.pod)
            uid = pod.uid or pod.key
            with self._serving(uid, pod.key, "prioritize") as rec:
                return self._prioritize(args, pod, uid, rec)

    def _prioritize(self, args: ExtenderArgs, pod: Pod, uid: str,
                    rec: Optional[_PodRecord]) -> HostPriorityList:
        if rec is not None:
            rec.pod = pod
            rec.span.mark("decode")
        ev = self._kept
        with self._mirror_mu:
            epoch = self._epoch
        if ev is not None and ev.uid == uid and ev.epoch == epoch:
            # this pod's `filter` evaluated it and the mirror has not
            # changed since: a new snapshot would encode the same rows
            if rec is not None:
                rec.eval_reused += 1
            EXTENDER_EVALUATIONS.inc(result="reused")
        else:
            ev = self._evaluate_pod(pod, uid, rec)

        names = self._candidates(args) or []
        slots = self._slots(names, ev.order)
        # float64 from here on, as Python's floats are: the integers are
        # those of round((s - lo) / span * MAX) over float(raw[i]), both
        # rounding half to even. -inf (infeasible, unknown) scores 0
        raw = np.where(slots >= 0, ev.scores[slots],
                       -np.inf).astype(np.float64)
        finite = raw != -np.inf
        lo, hi = (raw[finite].min(), raw[finite].max()) if finite.any() \
            else (0.0, 0.0)
        span = (hi - lo) or 1.0
        scaled = (np.where(finite, raw, lo) - lo) / span \
            * MAX_EXTENDER_PRIORITY
        scores = np.where(finite, np.rint(scaled), 0).astype(np.int64)
        return HostPriorityList(names, scores.tolist())

    # ------------------------------------------------------------------ #
    # verb: ProcessPreemption (extender.go:166-230)
    # ------------------------------------------------------------------ #

    def process_preemption(self, args: ExtenderPreemptionArgs) -> ExtenderPreemptionResult:
        with self._mu:
            pod = pod_from_v1(args.pod)

            # normalize both arg forms to {node: [victim pod keys or uids]}
            victims_by_node: Dict[str, List[str]] = {}
            if args.node_name_to_meta_victims:
                uid_to_key = {p.uid: p.key for p in self.cache.scheduled_pods()}
                for node, mv in args.node_name_to_meta_victims.items():
                    victims_by_node[node] = [uid_to_key.get(u, u) for u in mv.pods]
            else:
                for node, v in args.node_name_to_victims.items():
                    victims_by_node[node] = [pod_from_v1(p).key for p in v.pods]

            # NOTE: one what-if dispatch per candidate node (victim sets differ
            # per node, so the existing-pod arrays differ). This verb is the
            # reference's own cold path — the scheduler calls it once per
            # preemption attempt, not per cycle. The in-process preemptor
            # (ops/preempt.py) batches its what-ifs on device instead.
            result: Dict[str, MetaVictims] = {}
            all_scheduled = {p.key: p for p in self.cache.scheduled_pods()}
            key_to_uid = {p.key: p.uid for p in all_scheduled.values()}
            for node_name, victim_keys in victims_by_node.items():
                gone = set(victim_keys)
                keep = [p for k, p in all_scheduled.items() if k not in gone]
                probe = SchedulerCache()
                for n in self.cache.nodes():
                    probe.add_node(n)
                for p in keep:
                    probe.add_pod(p)
                snap, keys = self._snapshot_for(pod, cache=probe)
                mask = self._dispatch(snap, keys)[0][0]
                try:
                    i = snap.node_order.index(node_name)
                except ValueError:
                    continue
                if bool(mask[i]):
                    result[node_name] = MetaVictims(
                        pods=[key_to_uid.get(k, k) for k in victim_keys]
                    )
            return ExtenderPreemptionResult(node_name_to_meta_victims=result)

    # ------------------------------------------------------------------ #
    # verb: Bind
    # ------------------------------------------------------------------ #

    def bind(self, args: ExtenderBindingArgs) -> ExtenderBindingResult:
        with self._mu:
            key = f"{args.pod_namespace}/{args.pod_name}"
            # the placement changes what the next evaluation must see
            self._kept = None
            with self._serving(args.pod_uid or key, key, "bind") as rec:
                if rec is not None:
                    rec.span.mark("decode")
                res = self._bind(args, key, rec)
                if rec is not None:
                    rec.bound, rec.bind_error = not res.error, bool(res.error)
                    rec.span.mark("bind-commit")
                return res

    def _bind(self, args: ExtenderBindingArgs, key: str,
              rec: Optional[_PodRecord]) -> ExtenderBindingResult:
        """With a binder the Binding is this backend's to write: the pod is
        assumed on its node BEFORE the write, so that the next `filter`
        counts it whether or not the informer has echoed the Binding back
        yet; the echo confirms it (`observe_pod`), a refused write forgets
        it, an echo that never comes expires it (`filter`'s cleanup). The
        inside of the Binding rides the pod's record as `bind-commit`'s
        children (`assume`, `bind-call`, `finish`), as a wave's does."""
        if self.binder is None:
            self.bound.append((key, args.node))
            return ExtenderBindingResult()
        pod = rec.pod if rec is not None and rec.pod is not None \
            and rec.pod.key == key else None
        if pod is None and self.pod_lookup is not None:
            pod = self.pod_lookup(args.pod_namespace, args.pod_name)
        if pod is None:
            pod = self.cache.get_pod(key)
        if pod is None:
            # assuming a pod of unknown requests and labels would mirror
            # nothing of it: refuse rather than bind what cannot be counted
            return ExtenderBindingResult(
                error=f"bind {key}: the pod is unknown to the extender")
        if args.pod_uid and pod.uid != args.pod_uid:
            # the Binding names the caller's pod (extender.go:397 sends its
            # UID): the apiserver refuses it if that pod is gone
            pod = dataclasses.replace(pod, uid=args.pod_uid)
        tr = trace.current()
        t0 = time.perf_counter()
        assumed = self.cache.get_pod(key) is None
        if assumed:
            with self._mirror_mu:
                self.cache.assume_pod(pod, args.node)
                self._epoch += 1
        t1 = time.perf_counter()
        tok = None
        if tr is not None:
            tr.child("assume", t1 - t0)
            tok = tr.begin("bind-call")
        try:
            ok, err = self.binder(pod, args.node), ""
        except Exception as e:  # noqa: BLE001 — wire boundary
            ok, err = False, str(e)
        t2 = time.perf_counter()
        if tr is not None:
            tr.end(tok, t2 - t1)
        if ok:
            self.cache.finish_binding(key, self.telemetry.clock())
            self.bound.append((key, args.node))
        elif assumed:
            with self._mirror_mu:
                if self.cache.is_assumed(key):
                    self.cache.forget_pod(key)
                    self._epoch += 1
        if tr is not None:
            tr.child("finish", time.perf_counter() - t2)
        if not ok:
            return ExtenderBindingResult(
                error=err or f"bind {key} -> {args.node} failed")
        return ExtenderBindingResult()
