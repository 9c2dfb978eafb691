"""Scheduler cache: the host-side mirror of cluster state with the
assume/confirm/expire pod lifecycle and generation-diffed snapshots.

Mirrors the semantics of the reference's schedulerCache
(pkg/scheduler/internal/cache/cache.go):

  * AssumePod / FinishBinding / ForgetPod  (cache.go:283,304,328) — optimistic
    commit: the scheduler marks a pod as placed *before* the API write lands so
    the next cycle sees its resources; a TTL reaps assumed pods whose bind
    confirmation never arrives (expiry goroutine, cache.go:634-667 — here an
    explicit `cleanup(now)` with an injected clock, testable without sleeping).
  * AddPod confirms an assumed pod (cache.go:394-427); Update/RemovePod keep
    the mirror in sync with informer events (cache.go:429-517).
  * Add/Update/RemoveNode (cache.go:519-567).
  * UpdateNodeInfoSnapshot (cache.go:204-255): the reference walks a
    generation-ordered doubly-linked list of NodeInfos and copies only nodes
    whose generation is newer than the snapshot's. Here the same contract is a
    single monotonic `generation` plus per-node generations: `snapshot()`
    returns a cached `Snapshot` untouched when nothing changed, and re-encodes
    (host numpy staging → one device transfer) only when the generation moved.
    Unlike the reference there is no per-node copy loop to optimize away — the
    expensive artifact is the device-resident array set, rebuilt at most once
    per generation bump and reused across cycles with identical pending sets.

The reference's node_tree (internal/cache/node_tree.go:147 zone round-robin
iterator) has no analog here by design: it exists to spread *sampled* node
subsets across zones, and the TPU path evaluates the full (class × node)
lattice — spreading is handled by the PodTopologySpread scores natively
(SURVEY §2.3 "zone-balanced iteration").
"""

from __future__ import annotations

import functools
import os
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..api.types import Node, Pod
from ..component import trace
from .arrays import ClusterTables, NodeArrays, PodArrays
from .dims import Dims
from .encode import EMPTY_POD_ROW, Encoder


DEFAULT_ASSUME_TTL = 30.0  # durationToExpireAssumedPod, scheduler.go:268 (30s)

I32 = np.int32


@jax.jit
def _patch_rows(tree, idx, rows):
    """Scatter `rows` (a pytree of [k, …] updates) into device `tree` at row
    indices `idx` — the device half of the incremental snapshot
    (cache.go:204-255's per-NodeInfo copy, as one fused dynamic-update)."""
    return jax.tree.map(lambda a, r: a.at[idx].set(r), tree, rows)


@functools.partial(jax.jit, donate_argnums=(0,))
def _patch_rows_donated(tree, idx, rows):
    """The mesh-resident variant: the input buffers are DONATED, so XLA
    updates the resident sharded arrays in place (aliased output) instead of
    allocating a second copy of the whole node plane per cycle. Only callable
    when no in-flight dispatch still holds `tree` at the Python level — the
    cache's `_dispatch_inflight` gate (see `_patch_snapshot`)."""
    return jax.tree.map(lambda a, r: a.at[idx].set(r), tree, rows)


class ResidentDonationError(RuntimeError):
    """A donated mesh-resident patch silently COPIED instead of aliasing (the
    donated input buffer survived). On a real chip that means the resident-
    state design is paying 2× HBM and a full-plane copy per cycle without
    anyone noticing — fail loudly (ISSUE 3 acceptance: the donation assert
    proves the steady-state path never re-uploads the snapshot)."""


def _patch_resident(tree, idx, rows, donate: bool, cache=None):
    """One resident-buffer scatter. `donate=True` asserts the old buffers
    were actually consumed; set KTPU_MESH_DONATION_STRICT=0 to count-and-
    continue (cache.resident_donation_failures) on platforms whose runtime
    cannot alias (none of ours — CPU, GPU and TPU all donate)."""
    if not donate:
        return _patch_rows(tree, idx, rows)
    out = _patch_rows_donated(tree, idx, rows)
    leaves = [a for a in jax.tree.leaves(tree)]
    if leaves and not all(a.is_deleted() for a in leaves):
        if cache is not None:
            cache.resident_donation_failures += 1
        if os.environ.get("KTPU_MESH_DONATION_STRICT", "1") != "0":
            raise ResidentDonationError(
                "mesh-resident patch did not donate: "
                f"{sum(not a.is_deleted() for a in leaves)}/{len(leaves)} "
                "input buffers survived the scatter (silent full copy)")
    return out


def _pad_patch(idx: List[int], k_bucket: int) -> np.ndarray:
    """Pad the dirty-row index list to a bucketed length by repeating the
    first index — the repeated .set of identical values is idempotent, and
    bucketing keeps the patch kernel's compile count logarithmic."""
    out = np.full((k_bucket,), idx[0], I32)
    out[: len(idx)] = idx
    return out


def _patch_bucket(n: int) -> int:
    """Patch-scatter index bucket: pure powers of two, FLOORED at 64.

    Deliberately NOT dims.bucket(): that ladder runs eight rungs per
    octave — right for capacity dims, where padding waste multiplies
    through every engine plane, but each rung here is a distinct
    `_patch_rows` compile signature, and a first-seen rung is a ~0.5 s
    synchronous XLA compile in the middle of a wave. Streaming
    micro-waves (ISSUE 18), whose entire point is that a 3-pod admission
    finishes in milliseconds, cannot absorb that — under churn the varying
    dirty-row counts walked a new rung every few waves, each one a
    p99-destroying stall. A patch scatter's padding is idempotent
    repeated-index rows (microseconds of device work), so the coarse
    pow2-with-floor ladder costs nothing measurable and keeps the whole
    signature set small enough for warm_patch_ladder to precompile."""
    p = 64
    while p < n:
        p <<= 1
    return p


@dataclass
class _PodState:
    """podState (cache.go:52-58): the pod plus its assume bookkeeping."""

    pod: Pod
    assumed: bool = False
    binding_finished: bool = False
    deadline: Optional[float] = None  # set by finish_binding; None = no expiry
    bound_at: Optional[float] = None  # finish_binding's instant on the
                                      # cache's lag clock (confirm_waits)


class CacheError(RuntimeError):
    """Raised on lifecycle violations the reference treats as logic errors
    (cache.go returns errors / Fatalf on cache corruption, cache.go:445,473)."""


@dataclass
class Snapshot:
    """An immutable per-cycle view (nodeinfo/snapshot/snapshot.go): encoded
    device tables + the node-name order they were built in + the generation
    they reflect."""

    generation: int
    node_order: List[str]
    tables: object            # ClusterTables (device)
    existing: object          # PodArrays (device)
    pending: object           # PodArrays (device)
    dims: Dims
    pending_keys: Tuple[Tuple[str, int], ...]  # (pod key, object identity)
    existing_keys: Tuple[str, ...] = ()  # row order of `existing` (preemption
                                         # maps victim rows back to pod keys)
    gang: object = None  # GangArrays (ops/gang.py) when any pending pod is
                         # gang-grouped; None routes the plain engines
    device: object = None  # explicit placement of the device arrays (None =
                           # default). The dispatch supervisor routes
                           # degraded-mode snapshots to the CPU fallback so
                           # no cycle ever touches a lost backend's buffers.
    mesh: object = None  # jax.sharding.Mesh when the tables are resident
                         # sharded across the device mesh (node axis split,
                         # small tables replicated — parallel/mesh.py);
                         # keyed by IDENTITY: a reformed mesh is a new
                         # object, which forces re-shard from host staging.


class SchedulerCache:
    """Thread-safe pod/node mirror. A single writer (the event-handler thread)
    and a single reader (the scheduling loop) is the expected pattern, matching
    the reference's `cache.mu` discipline."""

    def __init__(self, ttl: float = DEFAULT_ASSUME_TTL) -> None:
        self._mu = threading.RLock()
        self._ttl = ttl
        self._nodes: Dict[str, Node] = {}
        self._pods: Dict[str, _PodState] = {}
        # what a Binding waits for after it is written: the informer's
        # confirmation. Both ends are read HERE on one clock of the
        # cache's own (time.perf_counter, the flight recorder's), since
        # add_pod is handed no `now` and finish_binding's `now` may be a
        # per-tick virtual clock. [count, sum_s, max_s] since the last
        # drain, and the assumed pods not yet confirmed — an index kept
        # wherever an assumed state enters or leaves (key -> state, in
        # assume order: `key in _assumed` iff `_pods[key].assumed`), so
        # that expiry and the counts range over IT (cache.go's assumedPods)
        # and never over the population.
        self.lag_clock = time.perf_counter
        self._confirm_waits: List[float] = [0, 0.0, 0.0]
        self._assumed: Dict[str, _PodState] = {}
        self._generation = 0
        self._snapshot: Optional[Snapshot] = None
        # ---- incremental snapshot state (cache.go:204-255 analog) ----
        # pods grouped by node: the unit of row re-encode is one node row
        self._by_node: Dict[str, Dict[str, Pod]] = {}
        self._dirty_nodes: Set[str] = set()           # rows to re-encode
        self._dirty_pods: Dict[str, Optional[Pod]] = {}  # key → Pod | None(del)
        # stable slot assignment: device row index per node / existing pod
        self._node_slot: Dict[str, int] = {}
        self._node_names: List[str] = []              # slot → name ("" freed)
        self._free_node_slots: List[int] = []
        self._pod_slot: Dict[str, int] = {}
        self._pod_keys: List[str] = []                # slot → key ("" freed)
        self._free_pod_slots: List[int] = []
        # host numpy staging mirrors of the device arrays
        self._staging_nodes: Optional[NodeArrays] = None
        self._staging_pod_rows: Optional[np.ndarray] = None   # [E, POD_ROW_COLS] i32
        self._staging_pod_valid: Optional[np.ndarray] = None  # [E] bool
        self._staging_pod_node: Optional[np.ndarray] = None   # [E] i32
        self._encoder: Optional[Encoder] = None
        self._reg_sizes: Dict[str, int] = {}
        self._n_topo_keys = 0
        # pending-batch staging (see _pending_block)
        self._pending_stage = None
        self._pending_stage_keys: Optional[Tuple] = None
        # the (class, pin) columns of the pending batch last put on the
        # device, on the host: what `pending_pins` counts
        self._pending_pin_cols: Optional[Tuple[np.ndarray, np.ndarray]] = None
        # introspection for tests/bench: how the last snapshot was produced
        self.last_snapshot_mode: str = ""   # "cached" | "patch" | "full"
        self.last_patch_rows: int = 0
        # assumed pods the last cleanup() looked at (the wave's record)
        self.last_cleanup_examined: int = 0
        # ---- mesh-resident accounting (ISSUE 3 donation contract) ----
        # full shard_tables uploads (cold / capacity growth / mesh reform)
        self.resident_full_uploads: int = 0
        # steady-state patches that DONATED the resident buffers (aliased
        # in-place update — the proof there is no full-snapshot device_put)
        self.resident_donated_patches: int = 0
        # patches that had to copy because a dispatch still held the front
        # buffer (the prestage half of the double-buffer — see
        # mark_dispatch_start)
        self.resident_copy_patches: int = 0
        # >0 while a dispatch holds the current snapshot's arrays at the
        # Python level: donating them would delete buffers a worker thread
        # is about to hand to XLA. The scheduler brackets submit→result
        # with mark_dispatch_start/done; prestage snapshots built inside
        # that window take the copy path (the back buffer of the double
        # buffer), and the next on-path snapshot donates the back buffer.
        self._dispatch_inflight: int = 0
        self._last_pending_patched = False
        # donated patches whose input buffers survived (silent copy) — only
        # grows in non-strict mode; strict mode raises instead
        self.resident_donation_failures: int = 0
        # gang groups: bound/assumed member count per group key (ops/gang.py
        # nets snapshot `needed` against these — minMember already satisfied
        # by running members doesn't have to re-place)
        self._group_bound: Dict[str, int] = {}
        # patch-scatter signatures already AOT-compiled by warm_patch_ladder
        # ((plane shapes, kb, donate) tuples — see the method)
        self._ladder_warmed: Set[Tuple] = set()

    # -- dirty-tracking helpers (callers hold self._mu) -- #

    def _pod_placed(self, pod: Pod) -> None:
        if pod.node_name:
            self._by_node.setdefault(pod.node_name, {})[pod.key] = pod
            self._dirty_nodes.add(pod.node_name)
        self._dirty_pods[pod.key] = pod
        gk = pod.group_key
        if gk:
            self._group_bound[gk] = self._group_bound.get(gk, 0) + 1

    def _pod_gone(self, pod: Pod) -> None:
        """`_pod_unplaced` for a pod that leaves the cache for good (removed,
        forgotten, expired), not for a newer object of itself: the volumes
        it alone named lose their owner (Encoder.vol_owner)."""
        self._pod_unplaced(pod)
        if pod.volumes and self._encoder is not None:
            self._encoder.release_volumes(pod)

    def _pod_unplaced(self, pod: Pod) -> None:
        if pod.node_name:
            self._by_node.get(pod.node_name, {}).pop(pod.key, None)
            self._dirty_nodes.add(pod.node_name)
        self._dirty_pods[pod.key] = None
        gk = pod.group_key
        if gk:
            c = self._group_bound.get(gk, 0) - 1
            if c > 0:
                self._group_bound[gk] = c
            else:
                self._group_bound.pop(gk, None)

    @property
    def node_count(self) -> int:
        with self._mu:
            return len(self._nodes)

    @property
    def pod_count(self) -> int:
        with self._mu:
            return len(self._pods)

    def group_bound_count(self, group_key: str) -> int:
        """Bound/assumed members of a gang group (the Coscheduling plugin's
        quorum source — assumed-but-waiting members count, exactly the set
        this cache mirrors)."""
        with self._mu:
            return self._group_bound.get(group_key, 0)

    # ------------------------------------------------------------------ #
    # pod lifecycle (cache.go:283-517)
    # ------------------------------------------------------------------ #

    def assume_pod(self, pod: Pod, node_name: str) -> None:
        """AssumePod (cache.go:283): optimistic placement of a scheduled pod."""
        with self._mu:
            key = pod.key
            if key in self._pods:
                raise CacheError(f"pod {key} is already in the cache")
            p = replace(pod, node_name=node_name)
            self._pods[key] = self._assumed[key] = \
                _PodState(pod=p, assumed=True)
            self._pod_placed(p)
            self._generation += 1

    def finish_binding(self, key: str, now: float) -> None:
        """FinishBinding (cache.go:304): the async bind goroutine completed its
        API write; start the expiry clock in case the confirming informer event
        never arrives."""
        with self._mu:
            st = self._pods.get(key)
            if st is None or not st.assumed:
                return  # finished binding for a pod no longer assumed: no-op
            st.binding_finished = True
            st.deadline = now + self._ttl
            st.bound_at = self.lag_clock()

    def forget_pod(self, key: str) -> None:
        """ForgetPod (cache.go:328): bind/permit/volume failure rollback."""
        with self._mu:
            st = self._pods.get(key)
            if st is None:
                return
            if not st.assumed:
                raise CacheError(f"pod {key} is bound, cannot forget")
            del self._pods[key]
            del self._assumed[key]
            self._pod_gone(st.pod)
            self._generation += 1

    def add_pod(self, pod: Pod) -> None:
        """AddPod (cache.go:394): informer confirmation. Confirms an assumed
        pod (clears its deadline) or inserts a pod scheduled by someone else."""
        with self._mu:
            key = pod.key
            st = self._pods.get(key)
            if st is not None and st.assumed:
                # confirmation — possibly onto a different node than assumed
                # (cache.go:404-410 logs and corrects)
                self._pod_unplaced(st.pod)
                self._pods[key] = _PodState(pod=pod)
                del self._assumed[key]
                if st.bound_at is not None:
                    lag = self.lag_clock() - st.bound_at
                    w = self._confirm_waits
                    w[0] += 1
                    w[1] += lag
                    if lag > w[2]:
                        w[2] = lag
            elif st is None:
                self._pods[key] = _PodState(pod=pod)
            else:
                raise CacheError(f"pod {key} was already added")
            self._pod_placed(pod)
            self._generation += 1

    def update_pod(self, pod: Pod) -> None:
        """UpdatePod (cache.go:429). Assumed pods are not updatable — the
        reference treats an update event for an assumed pod as corruption."""
        with self._mu:
            st = self._pods.get(pod.key)
            if st is None or st.assumed:
                raise CacheError(f"pod {pod.key} is not bound in the cache")
            self._pod_unplaced(st.pod)
            st.pod = pod
            self._pod_placed(pod)
            self._generation += 1

    def remove_pod(self, key: str) -> None:
        """RemovePod (cache.go:457)."""
        with self._mu:
            st = self._pods.get(key)
            if st is None:
                raise CacheError(f"pod {key} is not in the cache")
            del self._pods[key]
            if st.assumed:
                del self._assumed[key]
            self._pod_gone(st.pod)
            self._generation += 1

    def is_assumed(self, key: str) -> bool:
        with self._mu:
            st = self._pods.get(key)
            return bool(st and st.assumed)

    def forget_assumed(self) -> List[Pod]:
        """Drop EVERY assumed-but-unconfirmed pod (takeover reconciliation,
        sched/ledger.py replay): a new leader must rebuild its optimistic
        state from informer truth + the intent ledger, never trust assumes
        made before the fence — they may mirror a deposed reign's decisions
        the apiserver rejected. Returns the forgotten Pod objects (their
        node_name still carries the assumed placement) so the caller can
        requeue them even when no other record of them survives."""
        with self._mu:
            return [st.pod for st in self._drop_assumed(lambda st: True)]

    def drain_confirm_waits(self) -> Tuple[List[float], int]:
        """`([count, sum_s, max_s], assumed_outstanding)`: the lag from
        `finish_binding` to the informer's confirming `add_pod` over the
        confirmations since the last call (and resets it), and the assumed
        pods still unconfirmed now. The wave reads it at pop."""
        with self._mu:
            (n, total, worst), self._confirm_waits = \
                self._confirm_waits, [0, 0.0, 0.0]
            return ([n, round(total, 6), round(worst, 6)],
                    len(self._assumed))

    def pods_on_node(self, name: str) -> List[Pod]:
        """All pods (bound + assumed) occupying one node — the host-side
        feasibility check of intent replay reads this."""
        with self._mu:
            return list(self._by_node.get(name, {}).values())

    def get_node(self, name: str) -> Optional[Node]:
        with self._mu:
            return self._nodes.get(name)

    def invalidate_snapshot(self) -> None:
        """Force the next snapshot onto the FULL re-encode path (scratch
        staging + one device transfer), discarding the incremental state.
        This is the consistency sweep's self-heal (sched/debugger.py): when
        the patched staging arrays diverge from a from-scratch encode, the
        cheap fix is to stop trusting them."""
        with self._mu:
            self._snapshot = None
            self._staging_nodes = None
            self._staging_pod_rows = None
            self._staging_pod_valid = None
            self._staging_pod_node = None
            self._pending_stage = None
            self._pending_stage_keys = None
            self._generation += 1

    def get_pod(self, key: str) -> Optional[Pod]:
        with self._mu:
            st = self._pods.get(key)
            return st.pod if st else None

    # ------------------------------------------------------------------ #
    # node lifecycle (cache.go:519-567)
    # ------------------------------------------------------------------ #

    def add_node(self, node: Node) -> None:
        with self._mu:
            self._nodes[node.name] = node
            self._dirty_nodes.add(node.name)
            self._generation += 1

    def update_node(self, node: Node) -> None:
        with self._mu:
            self._nodes[node.name] = node
            self._dirty_nodes.add(node.name)
            self._generation += 1

    def remove_node(self, name: str) -> None:
        with self._mu:
            if name not in self._nodes:
                raise CacheError(f"node {name} is not in the cache")
            del self._nodes[name]
            self._dirty_nodes.add(name)
            self._generation += 1

    # ------------------------------------------------------------------ #
    # expiry (cache.go:634-667)
    # ------------------------------------------------------------------ #

    def cleanup(self, now: float) -> List[str]:
        """cleanupAssumedPods: drop assumed pods whose bind finished but whose
        confirming watch event never arrived within the TTL. Returns the
        expired keys (the reference logs a warning per pod, cache.go:657)."""
        with self._mu:
            self.last_cleanup_examined = len(self._assumed)
            return [st.pod.key for st in self._drop_assumed(
                lambda st: st.binding_finished and st.deadline is not None
                and now >= st.deadline)]

    def _drop_assumed(self, gone) -> List[_PodState]:
        """Take out of the cache the assumed pods of which `gone(state)`
        holds, in assume order, and return their states (caller holds
        `_mu`). The ONE place that finds assumed pods: it ranges over the
        assumed index, which `_pods`' own order restricted to assumed pods
        equals, since a pod enters both at `assume_pod`."""
        dropped = [st for st in self._assumed.values() if gone(st)]
        for st in dropped:
            key = st.pod.key
            del self._pods[key]
            del self._assumed[key]
            self._pod_gone(st.pod)
        if dropped:
            self._generation += 1
        return dropped

    # ------------------------------------------------------------------ #
    # snapshot (cache.go:204-255)
    # ------------------------------------------------------------------ #

    def scheduled_pods(self) -> List[Pod]:
        """All pods occupying node resources: bound + assumed."""
        with self._mu:
            return [st.pod for st in self._pods.values()]

    def nodes(self) -> List[Node]:
        with self._mu:
            return list(self._nodes.values())

    @property
    def generation(self) -> int:
        with self._mu:
            return self._generation

    def counts(self) -> Tuple[int, int, int]:
        """(nodes, total pods, assumed pods) — the cache-size gauges
        (cache.go:692-696)."""
        with self._mu:
            return len(self._nodes), len(self._pods), len(self._assumed)

    def mark_dispatch_start(self) -> None:
        """A dispatch now holds the current snapshot's device arrays (the
        scheduler calls this right before handing them to the watchdog
        worker). While in flight, mesh-resident patches must not donate —
        they take the copy path into the back buffer instead."""
        with self._mu:
            self._dispatch_inflight += 1

    def mark_dispatch_done(self) -> None:
        with self._mu:
            self._dispatch_inflight = max(self._dispatch_inflight - 1, 0)

    def snapshot(
        self,
        encoder: Encoder,
        pending: Sequence[Pod],
        base_dims: Optional[Dims] = None,
        extra_intern: Sequence[str] = (),
        device: object = None,
        mesh: object = None,
    ) -> Snapshot:
        """UpdateNodeInfoSnapshot analog (cache.go:204-255): return the cached
        encoded view when nothing changed; re-encode ONLY the dirty node/pod
        rows and scatter them into the resident device arrays when the change
        fits the existing capacities; fall back to a full encode + transfer
        only when a capacity (Dims) actually grows.

        The pending signature includes object identity, not just pod keys: a
        spec update flows through the queue as a *new* Pod object with the same
        namespace/name (queue.update), and scheduling it against the cached
        encoding of the old spec would pin it unschedulable forever."""
        pending_keys = tuple((p.key, id(p)) for p in pending)
        # on a traced wave the snapshot's parts are children of the phase
        # that asked for it: `prepare` (interning, slots, capacities),
        # then `full` or `patch`, and below those `upload` (_put)
        tr = trace.current()
        t0 = time.perf_counter()
        with self._mu:
            gen = self._generation
            snap = self._snapshot
            if snap is not None and snap.generation == gen \
                    and snap.pending_keys == pending_keys \
                    and snap.device == device and snap.mesh is mesh \
                    and (base_dims is None
                         or snap.dims == snap.dims.union(base_dims)) \
                    and self._reg_sizes == self._registry_sizes(encoder):
                # the base_dims guard: a caller may GROW the floor between
                # calls (the fleet bucket following another tenant's
                # growth) — a cached snapshot at the old capacities must
                # not short-circuit the re-encode that pads this tenant up.
                # The registry-sizes guard: the micro path (ISSUE 18)
                # interns its watch-delta pods BEFORE asking for the base
                # snapshot with an EMPTY pending batch — generation and
                # pending signature both unchanged — so a first-seen
                # request/labelset/class must fall through to the patch
                # path's grown-table rebuild, or the graft would score the
                # new pods against interned tables that end before their
                # ids (a wrong unschedulable verdict, not a crash).
                self.last_snapshot_mode = "cached"
                return snap

            for s in extra_intern:
                encoder.vocabs.label_keys.intern(s)
            projection_widened = False
            converged = False
            for _walk_pass in range(8):  # referenced keys grow monotonically
                encoder.intern_pods(pending)  # memoized batch: O(new)
                if (self._staging_nodes is None
                        or self._encoder is not encoder
                        or projection_widened):
                    # cold: walk everything (batch path)
                    encoder.intern_pods(
                        [st.pod for st in self._pods.values()])
                else:
                    encoder.intern_pods(
                        [p for p in self._dirty_pods.values()
                         if p is not None])   # steady state: O(changed)
                if not encoder.classes_stale:
                    converged = True
                    break
                # a selector referenced a new pod-label key mid-walk:
                # projected class identities (encode.py class_id) changed
                # for every pod — drop memos, re-walk ALL pods, and force
                # the full snapshot path (staged rows hold old class ids).
                # projection_rewalk clears classes_stale, so convergence is
                # tracked via the flag above, not re-checked after the loop.
                encoder.projection_rewalk()
                projection_widened = True
            if not converged:
                # an unconverged projection means staged class ids are
                # stale — a snapshot built now would schedule against the
                # wrong classes. Fail loud (encode.ProjectionUnconvergedError
                # semantics) instead of silently mis-placing.
                from .encode import ProjectionUnconvergedError

                raise ProjectionUnconvergedError(
                    "label projection did not converge after 8 re-walk "
                    "passes; "
                    f"{len(encoder.referenced_label_keys)} referenced keys")
            for name in self._dirty_nodes:
                n = self._nodes.get(name)
                if n is not None:
                    encoder.intern_node(n)

            # slot releases for removed nodes come FIRST so a same-window
            # remove+add nets out instead of growing capacity; then slot
            # allocation in node-insertion order so the lattice's node-index
            # tie-breaks are a deterministic function of event order. Slots
            # are decided here (not in the mutators) so they stay consistent
            # with the staging arrays even when snapshots are skipped.
            released_nodes: List[int] = []
            for name in [nm for nm in self._dirty_nodes
                         if nm not in self._nodes]:
                slot = self._node_slot.pop(name, None)
                if slot is None:
                    continue
                self._node_names[slot] = ""
                self._free_node_slots.append(slot)
                released_nodes.append(slot)
                if self._staging_nodes is not None:
                    for f in self._staging_nodes:
                        f[slot] = False if f.dtype == bool else (
                            0 if f.dtype == np.uint32 else -1)
                    self._staging_nodes.alloc[slot] = 0
                    self._staging_nodes.used[slot] = 0
                    self._staging_nodes.label_ints[slot] = 0
                    self._staging_nodes.vol_cnt[slot] = 0
                # pods still bound to the vanished node must stop pointing at
                # the freed slot (a later node may reuse it); re-row them
                for key, p in self._by_node.get(name, {}).items():
                    self._dirty_pods.setdefault(key, p)
            dirty_live: List[Node] = []  # in _nodes' order, for dims below
            for name, node in self._nodes.items():
                if name not in self._dirty_nodes:
                    continue
                dirty_live.append(node)
                if name not in self._node_slot:
                    if self._free_node_slots:
                        slot = self._free_node_slots.pop()
                        self._node_names[slot] = name
                    else:
                        slot = len(self._node_names)
                        self._node_names.append(name)
                    self._node_slot[name] = slot
                    # pods that bound to this node while it had no slot (watch
                    # ordering / node re-add) carry node_id=-1 rows; re-row
                    # them so counts and victim discovery see them again
                    for key, p in self._by_node.get(name, {}).items():
                        self._dirty_pods.setdefault(key, p)
            pod_frees = len(self._free_pod_slots) + sum(
                1 for k, p in self._dirty_pods.items()
                if p is None and k in self._pod_slot)
            new_pods = sum(1 for k, p in self._dirty_pods.items()
                           if p is not None and k not in self._pod_slot)
            n_pod_slots = len(self._pod_keys) + max(new_pods - pod_frees, 0)

            # the nodes whose topology domains can be new to the encoder:
            # on the patch path's own terms (resident staging under this
            # encoder, projection as it was, no topology key since the last
            # snapshot) only the dirty ones, since every other node was
            # registered under this key count and register_node_domains'
            # memo would answer for it; every node otherwise
            if (self._staging_nodes is not None
                    and self._encoder is encoder
                    and not projection_widened
                    and len(encoder.vocabs.topo_keys) == self._n_topo_keys):
                domain_nodes = dirty_live
            else:
                domain_nodes = list(self._nodes.values())
            d = encoder.dims(
                len(self._node_names), n_pod_slots, len(pending),
                domain_nodes,
                # capacities are monotonic ACROSS cycles: seed from the live
                # snapshot so a smaller pending batch doesn't shrink P and
                # masquerade as a capacity change. The seed is the UNION of
                # the live snapshot's dims and the caller's base_dims — the
                # fleet layer (fleet/tables.py) grows the shared tenant
                # bucket when ANY tenant grows, and every other tenant's
                # snapshot must follow it up (stacked emission: one vmap'd
                # program serves all tenants, so their shapes must agree)
                snap.dims.union(base_dims) if snap is not None
                else base_dims,
            )
            # the engine-routing flag is per-batch, not a capacity: it must
            # not force a full re-encode when it flips
            d = replace(d, has_node_name=any(p.node_name for p in pending))
            if mesh is not None:
                # the node axis must divide the mesh evenly so each chip
                # owns N/n_devices rows; pad the CAPACITY (extra slots are
                # inert exactly like any unoccupied bucket slot) rather
                # than padding arrays post-hoc, so staging and resident
                # shapes agree and the patch scatter stays shape-stable
                from ..parallel.mesh import padded_node_count

                nd = len(mesh.devices.flat)
                if d.N % nd:
                    d = replace(d, N=padded_node_count(d.N, nd))

            full = (
                snap is None
                or self._staging_nodes is None
                or self._encoder is not encoder
                or projection_widened
                # placement change (degradation onto the CPU fallback, or
                # recovery back to the primary): the resident arrays live
                # on the WRONG — possibly dead — device, so the patch
                # path's scatter-into-resident is unusable; rebuild from
                # the host staging, which never left the host
                or snap.device != device
                # mesh change (first shard, reform after device loss, or
                # drop to single-device): resident buffers carry the OLD
                # sharding — re-shard from host staging
                or snap.mesh is not mesh
                or replace(d, has_node_name=False)
                != replace(snap.dims, has_node_name=False)
            )
            if tr is not None:
                t1 = time.perf_counter()
                tr.child("prepare", t1 - t0)
                tok = tr.begin("full" if full else "patch")
            try:
                if full:
                    return self._full_snapshot(
                        encoder, pending, pending_keys, gen, d, base_dims,
                        device, mesh)
                return self._patch_snapshot(
                    encoder, pending, pending_keys, gen, d, snap,
                    released_nodes, device, mesh)
            finally:
                if tr is not None:
                    tr.end(tok, time.perf_counter() - t1)

    def micro_graft(self, encoder: Encoder, pending: Sequence[Pod],
                    base: Snapshot, micro_p: int,
                    device: object = None, mesh: object = None) -> Snapshot:
        """Micro-wave pending graft (ISSUE 18 streaming admission): an
        EPHEMERAL Snapshot sharing `base`'s resident cluster tables and
        existing-pod arrays (the double-buffered device state stays
        untouched — the caller just brought it current via the ordinary
        generation-diffed `snapshot()` with an empty pending batch) with a
        small standalone [micro_p] pending block for the watch-delta pods.

        The graft is NOT stored as `_snapshot`: the cached resident view
        keeps diffing against the bulk pipeline's snapshots, so a micro
        wave between two bulk waves costs the bulk path nothing. Dims are
        `base.dims` with only P swapped to the fixed micro capacity (and
        has_node_name False — queue eligibility excludes pinned pods), so
        every micro wave of a given cluster shape shares ONE compile
        signature regardless of how many deltas coalesced. The caller
        must have interned `pending` into `encoder` BEFORE building
        `base` (cycle.micro_snapshot_with_keys does), so any registry or
        capacity growth the new pods cause is already reflected in
        `base.dims`/`base.tables`."""
        d = replace(base.dims, P=micro_p, has_node_name=False)
        with self._mu:
            pe_host = encoder.build_pod_arrays(
                list(pending), d, self._node_slot, capacity=d.P)
            self._pending_pin_cols = (pe_host.cls, pe_host.pin)
            gang = self._gang_arrays(encoder, pending, d, mesh)
        return Snapshot(
            generation=base.generation,
            node_order=base.node_order,
            tables=base.tables,
            existing=base.existing,
            pending=self._put(pe_host, device, mesh),
            dims=d,
            pending_keys=tuple((p.key, id(p)) for p in pending),
            existing_keys=base.existing_keys,
            gang=gang,
            device=device,
            mesh=mesh,
        )

    def warm_patch_ladder(self, snap: Snapshot, mesh=None) -> int:
        """Pre-populate the patch-scatter compile ladder for `snap`'s
        resident planes (nodes / existing / pending) by driving real
        no-op scatters through the live jit dispatch path.

        Each `_patch_rows` specialization is keyed by (plane shapes, index
        bucket); with `_patch_bucket`'s floor the ladder per plane is
        {64, 128, ..., capacity}, and without this warm each rung costs a
        synchronous ~0.5 s XLA compile the first wave that dirties that
        many rows — exactly the stall profile streaming micro-waves
        (ISSUE 18) cannot absorb, since their entire point is that a
        3-pod wave finishes in milliseconds. The warm must be a REAL call,
        not `.lower().compile()`: an AOT-compiled object is a separate
        executable and does not seed the tracing cache the live dispatch
        consults, so an abstract warm leaves the first live wave paying
        the full compile anyway (measured: 0.44 s after a same-process
        abstract warm). A real scatter of row 0's own value at index 0 is
        idempotent on the output and the non-donated input is never
        mutated, so warming against the live resident tree is safe; the
        donated variant warms against a host-roundtrip copy so the
        resident buffers are not consumed. Returns the number of
        signatures compiled by THIS call; repeat calls are cheap
        (memoized on plane shapes). Safe to run from a background thread —
        jit dispatch is thread-safe and the warm never mutates the cache."""
        from ..sched.telemetry import xla_scope

        # every rung is the XLA account's under the planes' capacities,
        # whoever warms: the prewarmer's thread, an extender's start, a
        # caller of its own
        with xla_scope("patch-ladder",
                       {"N": snap.dims.N, "E": snap.dims.E, "P": snap.dims.P,
                        "mesh": mesh is not None}):
            return self._warm_patch_ladder(snap, mesh)

    def _warm_patch_ladder(self, snap: Snapshot, mesh) -> int:
        compiled = 0
        for tree in (snap.tables.nodes, snap.existing, snap.pending):
            leaves = jax.tree.leaves(tree)
            if not leaves:
                continue
            # top rung: _patch_bucket(cap), not cap — capacities are
            # eight-per-octave (dims.bucket) or mesh-padded, i.e. usually
            # non-pow2, and the live ladder rounds up past them
            top = _patch_bucket(int(leaves[0].shape[0]))
            shapes = tuple((tuple(a.shape), str(a.dtype)) for a in leaves)
            kb = 64
            while True:
                for donate in ((False, True) if mesh is not None
                               else (False,)):
                    key = (shapes, kb, donate)
                    if key in self._ladder_warmed:
                        continue
                    self._ladder_warmed.add(key)
                    idx = np.zeros((kb,), I32)
                    # rows match the live call exactly: host numpy, same
                    # trailing shape per leaf. Zero payload is fine — the
                    # output is discarded.
                    rows = jax.tree.map(
                        lambda a, _kb=kb: np.zeros(
                            (_kb,) + tuple(a.shape[1:]), a.dtype), tree)
                    try:
                        if donate:
                            # donation consumes its input: warm against a
                            # throwaway copy (host roundtrip preserves the
                            # sharding without aliasing the resident tree)
                            scratch = jax.tree.map(
                                lambda a: jax.device_put(
                                    np.asarray(a),
                                    getattr(a, "sharding", None)), tree)
                            out = _patch_rows_donated(scratch, idx, rows)
                        else:
                            out = _patch_rows(tree, idx, rows)
                        jax.block_until_ready(out)
                        compiled += 1
                    except Exception:  # noqa: BLE001 - warm is an
                        # optimization, never fatal; the live path compiles
                        # on demand exactly as without the ladder
                        self._ladder_warmed.discard(key)
                if kb >= top:
                    break
                kb *= 2
        return compiled

    def pending_pins(self, k: int) -> Tuple[int, int, Optional[np.ndarray]]:
        """Of the first `k` pods of the pending batch last put on the device:
        how many carry a pin (`PodArrays.pin`), how many classes hold them,
        and which they are ([k] bool; None where none is). Two vectorized
        passes over host columns the snapshot built anyway."""
        with self._mu:
            cols = self._pending_pin_cols
        if cols is None:
            return 0, 0, None
        which = cols[1][:k] >= 0
        n = int(np.count_nonzero(which))
        if not n:
            return 0, 0, None
        return n, int(np.unique(cols[0][:k][which]).size), which

    def pending_extended(self, encoder: Encoder,
                         k: int) -> Dict[str, np.ndarray]:
        """Of the first `k` pods of the pending batch last put on the device:
        per extended resource name, which ask it ([k] bool). From the class
        column and each distinct class's request row; {} where the encoder
        has seen no extended resource at all."""
        if not len(encoder.vocabs.resources):
            return {}
        with self._mu:
            cols = self._pending_pin_cols
        if cols is None:
            return {}
        cls = cols[0][:k]
        by_name: Dict[str, List[int]] = {}
        for c in np.unique(cls):
            for name in encoder.class_extended(int(c)):
                by_name.setdefault(name, []).append(int(c))
        return {name: np.isin(cls, cs) for name, cs in by_name.items()}

    @staticmethod
    def _registry_sizes(encoder: Encoder) -> Dict[str, int]:
        return {
            "reqs": len(encoder.req_reg),
            "labelsets": len(encoder.labelset_reg),
            "nterms": len(encoder.nterm_reg),
            "tolsets": len(encoder.tolset_reg),
            "portsets": len(encoder.portset_reg),
            "terms": len(encoder.term_reg),
            "classes": len(encoder.class_reg),
            "images": len(encoder.vocabs.images),
            "volsets": len(encoder.volset_reg),
        }

    def _gang_arrays(self, encoder: Encoder, pending, d: Dims,
                     mesh: object = None):
        """Per-cycle GangArrays for the pending batch, netting each group's
        `needed` against members already bound/assumed in this cache. Host
        Python over every pending pod: on a gang-bearing batch its time is
        the `gang` child of the phase that took the snapshot."""
        tr = trace.current()
        t0 = time.perf_counter() if tr is not None else 0.0
        bound = {encoder.pod_groups.get(gk): c
                 for gk, c in self._group_bound.items()
                 if encoder.pod_groups.get(gk) >= 0}
        g = encoder.build_gang_arrays(list(pending), d, bound)
        if g is not None and mesh is not None:
            g = self._put(g, None, mesh)  # replicate: read by every shard
        if g is not None and tr is not None:
            tr.child("gang", time.perf_counter() - t0)
        return g

    def _existing_pod_arrays(self, d: Dims) -> PodArrays:
        rows = self._staging_pod_rows
        return PodArrays(
            valid=self._staging_pod_valid[: d.E],
            name_id=rows[: d.E, 0], ns=rows[: d.E, 1], cls=rows[: d.E, 2],
            priority=rows[: d.E, 3], creation=rows[: d.E, 4],
            node_id=self._staging_pod_node[: d.E],
            node_name_req=rows[: d.E, 5], pin=rows[: d.E, 6],
        )

    @staticmethod
    def _replicated(mesh):
        """NamedSharding for the replicated leaves (pending/existing/indices)
        of a mesh-resident snapshot."""
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(mesh, PartitionSpec())

    def _put(self, tree, device, mesh):
        """Route host arrays to their serving placement: replicated across
        the mesh when one is active, else onto `device` (None = default)."""
        tr = trace.current()
        t0 = time.perf_counter() if tr is not None else 0.0
        if mesh is not None:
            out = jax.device_put(tree, self._replicated(mesh))
        else:
            out = jax.device_put(tree, device)
        if tr is not None:
            tr.child("upload", time.perf_counter() - t0)
        return out

    def _full_snapshot(self, encoder, pending, pending_keys, gen, d,
                       base_dims: Optional[Dims] = None,
                       device: object = None,
                       mesh: object = None) -> Snapshot:
        """Cold path: rebuild staging + every device table. Runs when
        capacities grow (recompile territory anyway) or on first use."""
        self.last_snapshot_mode = "full"
        # compact, stable slot assignment
        live_nodes = [nm for nm in self._node_names if nm in self._nodes]
        for nm in self._nodes:
            if nm not in self._node_slot:
                live_nodes.append(nm)
        self._node_names = live_nodes
        self._node_slot = {nm: i for i, nm in enumerate(live_nodes)}
        self._free_node_slots = []
        self._pod_keys = list(self._pods.keys())
        self._pod_slot = {k: i for i, k in enumerate(self._pod_keys)}
        self._free_pod_slots = []

        nodes = [self._nodes[nm] for nm in self._node_names]
        # full re-encode rebuilds every row anyway — the free moment to
        # compact churn-accumulated domain ids (hostname-keyed spread makes
        # every node name ever seen a domain otherwise) and shrink D back
        from .dims import bucket
        encoder.rebuild_domain_maps(nodes)
        max_dom = max((len(dm) for dm in encoder.domain_maps), default=1)
        floor_d = (base_dims.D if base_dims is not None else Dims().D)
        new_D = max(bucket(max_dom), floor_d)
        if new_D < d.D:
            d = replace(d, D=new_D)
        # same for gang group ids: finished jobs would otherwise grow GR
        # (and the full-re-encode cadence) forever
        encoder.compact_groups(
            [st.pod for st in self._pods.values()] + list(pending))
        floor_gr = (base_dims.GR if base_dims is not None else Dims().GR)
        new_GR = max(bucket(max(len(encoder.pod_groups), 1)), floor_gr)
        if new_GR < d.GR:
            d = replace(d, GR=new_GR)
        self._staging_nodes = encoder.empty_node_arrays(d)
        for i, n in enumerate(nodes):
            encoder.encode_node_row(
                self._staging_nodes, i, n,
                list(self._by_node.get(n.name, {}).values()), d)

        self._staging_pod_rows = np.tile(
            np.array(EMPTY_POD_ROW, I32), (d.E, 1))
        self._staging_pod_valid = np.zeros((d.E,), bool)
        self._staging_pod_node = np.full((d.E,), -1, I32)
        for i, k in enumerate(self._pod_keys):
            p = self._pods[k].pod
            self._staging_pod_rows[i] = encoder.pod_row(p)
            self._staging_pod_valid[i] = True
            self._staging_pod_node[i] = self._node_slot.get(p.node_name, -1)

        tables = ClusterTables(
            nodes=self._staging_nodes,
            reqs=encoder.build_req_table(d),
            labelsets=encoder.build_labelset_table(d),
            nterms=encoder.build_nterm_table(d),
            tolsets=encoder.build_tolset_table(d),
            portsets=encoder.build_portset_table(d),
            terms=encoder.build_term_table(d),
            classes=encoder.build_class_table(d),
            images=encoder.build_image_table(d),
            zone_keys=encoder.build_zone_keys(),
            volsets=encoder.build_volset_table(d),
            drv_masks=encoder.build_drv_masks(d),
        )
        pe = encoder.build_pod_arrays(list(pending), d, self._node_slot,
                                      capacity=d.P)
        self._pending_pin_cols = (pe.cls, pe.pin)
        if mesh is not None:
            # mesh-resident placement: node axis split across the mesh's
            # chips, small interned tables replicated (parallel/mesh.py);
            # pending/existing replicate — they are read by every chip's
            # shard of the lattice. This is the ONE full upload; steady
            # state patches the resident shards (see _patch_snapshot).
            from ..parallel.mesh import shard_tables

            tables_dev = shard_tables(tables, mesh)
            self.resident_full_uploads += 1
        else:
            tables_dev = jax.device_put(tables, device)
        snap = Snapshot(
            generation=gen,
            node_order=list(self._node_names),
            tables=tables_dev,
            existing=self._put(self._existing_pod_arrays(d), device, mesh),
            pending=self._put(pe, device, mesh),
            dims=d,
            pending_keys=pending_keys,
            existing_keys=tuple(self._pod_keys),
            gang=self._gang_arrays(encoder, pending, d, mesh),
            device=device,
            mesh=mesh,
        )
        self._encoder = encoder
        self._reg_sizes = self._registry_sizes(encoder)
        self._n_topo_keys = len(encoder.vocabs.topo_keys)
        # the pending stage holds rows interned under THIS encoder's
        # vocabularies; a full re-encode (possibly with a fresh encoder)
        # makes them unusable for diffing
        self._pending_stage = None
        self._pending_stage_keys = None
        self._dirty_nodes.clear()
        self._dirty_pods.clear()
        self.last_patch_rows = len(self._node_names)
        self._snapshot = snap
        return snap

    def _patch_snapshot(self, encoder, pending, pending_keys, gen, d,
                        snap: Snapshot,
                        released_nodes: Sequence[int] = (),
                        device: object = None,
                        mesh: object = None) -> Snapshot:
        """Steady-state path: O(changed) host work, O(changed) device scatter.
        This is what makes `state/encode.py`'s "patched incrementally" promise
        true — no full re-encode, no full re-upload.

        Mesh-resident mode adds the donation/double-buffer contract: when no
        dispatch holds the resident buffers (the usual on-path snapshot), the
        scatter DONATES them — XLA aliases the update in place, and
        `_patch_resident` raises if the runtime silently copied. When a
        dispatch IS in flight (the scheduler's prestage snapshot, built while
        the device still evaluates cycle N), the scatter copies into a back
        buffer instead — that copy is what lets cycle N+1's delta upload
        overlap cycle N's dispatch, and the NEXT on-path patch donates the
        back buffer."""
        self.last_snapshot_mode = "patch"
        from .dims import bucket

        donate = mesh is not None and self._dispatch_inflight == 0
        patched_resident = False

        # --- new topology keys: backfill only the new [N] topo column(s) ---
        # A never-seen topologyKey used to force the ~full-encode fallback
        # (every node row owns a cell in the [N, K] topo plane). As long as
        # the key fits the existing K/D capacities (Dims unchanged — the
        # caller already checked), the column is a pure function of node
        # labels the staging mirror already holds: derive it host-side in
        # O(N·new_keys) dict lookups and ship the 4·N·K-byte plane, keeping
        # an adversarial label stream on the patch path.
        nk = len(encoder.vocabs.topo_keys)
        topo_grew = nk != self._n_topo_keys
        if topo_grew:
            for ki in range(self._n_topo_keys, nk):
                key = encoder.vocabs.topo_keys.lookup(ki)
                dm = (encoder.domain_maps[ki]
                      if ki < len(encoder.domain_maps) else {})
                for slot, nm in enumerate(self._node_names):
                    n = self._nodes.get(nm)
                    val = n.labels.get(key) if n is not None else None
                    if val is None:
                        continue
                    vid = encoder.vocabs.label_vals.get(val)
                    # both planes, exactly as encode_node_row writes them:
                    # `topo` (label-value id) and `domain` (compact domain id
                    # — what interpod/topospread kernels actually read)
                    self._staging_nodes.topo[slot, ki] = vid
                    self._staging_nodes.domain[slot, ki] = dm.get(vid, -1)
            self._n_topo_keys = nk

        # --- node rows (removed nodes were already cleared in snapshot()) ---
        node_idx: List[int] = list(released_nodes)
        for name in sorted(self._dirty_nodes):
            n = self._nodes.get(name)
            if n is None:
                continue
            slot = self._node_slot[name]
            encoder.encode_node_row(
                self._staging_nodes, slot, n,
                list(self._by_node.get(name, {}).values()), d)
            node_idx.append(slot)

        tables = snap.tables
        if topo_grew:
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                from ..parallel.mesh import NODE_AXIS

                node_sh = NamedSharding(mesh, PartitionSpec(NODE_AXIS))
                put_topo = lambda a: jax.device_put(
                    np.ascontiguousarray(a), node_sh)
            else:
                put_topo = lambda a: jax.device_put(
                    np.ascontiguousarray(a), device)
            tables = tables._replace(
                nodes=tables.nodes._replace(
                    topo=put_topo(self._staging_nodes.topo),
                    domain=put_topo(self._staging_nodes.domain)),
                zone_keys=self._put(encoder.build_zone_keys(), device, mesh))
        if node_idx:
            kb = _patch_bucket(len(node_idx))
            idx = _pad_patch(node_idx, kb)
            rows = NodeArrays(*[np.ascontiguousarray(f[idx])
                                for f in self._staging_nodes])
            # indices ride device_put WITH the snapshot's placement: a bare
            # jnp.asarray would materialize on the default (possibly lost)
            # backend even when the rest of the patch targets the fallback
            tables = tables._replace(
                nodes=_patch_resident(tables.nodes,
                                      self._put(idx, device, mesh),
                                      self._put(rows, device, mesh)
                                      if mesh is not None else rows,
                                      donate, self))
            patched_resident = True

        # --- small interned tables: rebuild only the ones whose registry grew
        sizes = self._registry_sizes(encoder)
        if sizes != self._reg_sizes:
            builders = {
                "reqs": encoder.build_req_table,
                "labelsets": encoder.build_labelset_table,
                "nterms": encoder.build_nterm_table,
                "tolsets": encoder.build_tolset_table,
                "portsets": encoder.build_portset_table,
                "terms": encoder.build_term_table,
                "classes": encoder.build_class_table,
                "images": encoder.build_image_table,
                "volsets": encoder.build_volset_table,
            }
            tables = tables._replace(**{
                k: self._put(builders[k](d), device, mesh)
                for k in builders if sizes[k] != self._reg_sizes[k]
            })
            if sizes["volsets"] != self._reg_sizes["volsets"]:
                # a set the registry had not seen may name a volume the
                # vocab had not: its bit belongs to its driver's mask
                tables = tables._replace(drv_masks=self._put(
                    encoder.build_drv_masks(d), device, mesh))
            self._reg_sizes = sizes

        # --- existing-pod rows: removals first so a same-window remove+add
        # reuses the freed slot instead of growing past capacity ---
        pod_idx: List[int] = []
        for key in sorted(self._dirty_pods):
            if self._dirty_pods[key] is not None:
                continue
            slot = self._pod_slot.pop(key, None)
            if slot is None:
                continue
            self._pod_keys[slot] = ""
            self._free_pod_slots.append(slot)
            self._staging_pod_valid[slot] = False
            self._staging_pod_rows[slot] = EMPTY_POD_ROW
            self._staging_pod_node[slot] = -1
            pod_idx.append(slot)
        for key in sorted(self._dirty_pods):
            pod = self._dirty_pods[key]
            if pod is None:
                continue
            slot = self._pod_slot.get(key)
            if slot is None:
                if self._free_pod_slots:
                    slot = self._free_pod_slots.pop()
                    self._pod_keys[slot] = key
                else:
                    slot = len(self._pod_keys)
                    self._pod_keys.append(key)
                self._pod_slot[key] = slot
            self._staging_pod_rows[slot] = encoder.pod_row(pod)
            self._staging_pod_valid[slot] = True
            self._staging_pod_node[slot] = self._node_slot.get(
                pod.node_name, -1)
            pod_idx.append(slot)

        existing = snap.existing
        if pod_idx:
            kb = _patch_bucket(len(pod_idx))
            idx = _pad_patch(pod_idx, kb)
            host = self._existing_pod_arrays(d)
            rows = PodArrays(*[np.ascontiguousarray(f[idx]) for f in host])
            existing = _patch_resident(
                existing, self._put(idx, device, mesh),
                self._put(rows, device, mesh) if mesh is not None else rows,
                donate, self)
            patched_resident = True

        # --- pending: identity-diffed against the previous batch ---
        # The unschedulable/backoff queues feed largely the SAME pod
        # objects cycle after cycle (the reference's queues hold object
        # references; our encoder memoizes rows by object identity), so
        # when the batch mostly repeats, only the changed slots are
        # re-derived on a persistent staging block — the pod-axis analog
        # of the generation-diffed node snapshot (cache.go:204-255).
        if pending_keys == snap.pending_keys:
            pe = snap.pending
        else:
            self._last_pending_patched = False
            pe = self._pending_block(encoder, pending, pending_keys, d,
                                     snap.pending, device, mesh, donate)
            patched_resident = patched_resident or self._last_pending_patched

        if mesh is not None and patched_resident:
            if donate:
                self.resident_donated_patches += 1
            else:
                self.resident_copy_patches += 1
        new_snap = Snapshot(
            generation=gen,
            node_order=list(self._node_names),
            tables=tables,
            existing=existing,
            pending=pe,
            dims=d,
            pending_keys=pending_keys,
            existing_keys=tuple(self._pod_keys),
            gang=self._gang_arrays(encoder, pending, d, mesh),
            device=device,
            mesh=mesh,
        )
        self._dirty_nodes.clear()
        self._dirty_pods.clear()
        self.last_patch_rows = len(node_idx) + len(pod_idx)
        self._snapshot = new_snap
        return new_snap


    def _pending_block(self, encoder, pending, pending_keys, d: Dims,
                       prev_device, device: object = None,
                       mesh: object = None, donate: bool = False):
        """Pending PodArrays, identity-diffed against the previous batch:
        when the batch largely repeats, only the changed slots re-derive on
        the persistent host stage and SCATTER into the resident device
        arrays — the same `_patch_rows` + bucketed-index pattern the node
        and existing-pod rows use, so one changed pod costs one small
        scatter, never a full [P] re-upload. Falls back to the full
        vectorized assembly when the shape changed or most slots differ
        (fresh batch churn — the diff would cost more than it saves)."""
        from .dims import bucket

        prev_keys = self._pending_stage_keys
        stage = self._pending_stage
        # nodeName-bearing batches route to the scan engine and carry slot
        # references that can go stale when node slots churn — they take
        # the full assembly, not the diff
        if (stage is not None and prev_keys is not None
                and not d.has_node_name
                and stage.valid.shape[0] == d.P
                and len(prev_keys) == len(pending_keys)):
            changed = [i for i, (a, b) in enumerate(
                zip(prev_keys, pending_keys)) if a != b]
            if len(changed) <= max(len(pending_keys) // 8, 32):
                for i in changed:
                    p = pending[i]
                    stage.rows[i] = encoder.pod_row(p)
                    stage.node_id[i] = self._node_slot.get(
                        p.node_name, -1) if p.node_name else -1
                    stage.valid[i] = True
                self._pending_stage_keys = pending_keys
                self._pending_pin_cols = (stage.rows[:, 2], stage.rows[:, 6])
                kb = _patch_bucket(len(changed))
                idx = _pad_patch(changed, kb)
                rows = PodArrays(
                    valid=stage.valid[idx],
                    name_id=np.ascontiguousarray(stage.rows[idx, 0]),
                    ns=np.ascontiguousarray(stage.rows[idx, 1]),
                    cls=np.ascontiguousarray(stage.rows[idx, 2]),
                    priority=np.ascontiguousarray(stage.rows[idx, 3]),
                    creation=np.ascontiguousarray(stage.rows[idx, 4]),
                    node_id=stage.node_id[idx],
                    node_name_req=np.ascontiguousarray(stage.rows[idx, 5]),
                    pin=np.ascontiguousarray(stage.rows[idx, 6]),
                )
                self._last_pending_patched = True
                return _patch_resident(
                    prev_device, self._put(idx, device, mesh),
                    self._put(rows, device, mesh) if mesh is not None
                    else rows, donate, self)
        pe_host = encoder.build_pod_arrays(
            list(pending), d, self._node_slot, capacity=d.P)
        self._pending_stage = _PendingStage.from_pod_arrays(pe_host)
        self._pending_stage_keys = pending_keys
        self._pending_pin_cols = (pe_host.cls, pe_host.pin)
        return self._put(pe_host, device, mesh)


class _PendingStage:
    """Persistent host staging for the pending batch ([P, POD_ROW_COLS] rows +
    node_id + valid), patched in place across cycles."""

    __slots__ = ("rows", "node_id", "valid")

    def __init__(self, rows, node_id, valid):
        self.rows = rows
        self.node_id = node_id
        self.valid = valid

    @classmethod
    def from_pod_arrays(cls, pe: PodArrays) -> "_PendingStage":
        rows = np.stack([pe.name_id, pe.ns, pe.cls, pe.priority,
                         pe.creation, pe.node_name_req, pe.pin], axis=1)
        return cls(rows=np.ascontiguousarray(rows),
                   node_id=np.array(pe.node_id, copy=True),
                   valid=np.array(pe.valid, copy=True))



class FakeCache(SchedulerCache):
    """Test double in the spirit of internal/cache/fake/fake_cache.go — a real
    cache with a controllable clock convenience."""

    def expire_all_assumed(self) -> List[str]:
        with self._mu:
            return [st.pod.key for st in self._drop_assumed(
                lambda st: st.binding_finished)]
