"""FleetServer: K virtual tenant clusters behind one resident scheduler.

Ownership model (the "one resident scheduler" of ROADMAP item 1):

  * ONE supervisor — every fleet dispatch runs under the watchdog/fallback
    ladder (sched/supervisor.py), keyed by the fleet signature.
  * ONE prewarmer — the stacked executable AOT-compiles under the fleet
    key slot (sched/prewarm.py `fleet=`), so a K-tenant Compiled and a
    single-cluster one can never cross.
  * ONE event-ingest surface — callers route watch events to
    `tenant(name).on_pod_add(...)` etc.; a production informer set routes
    by tenant label on one watch stream (docs/FLEET.md).
  * K per-tenant Schedulers — each tenant keeps its OWN cache, queue,
    encoder, BindIntentLedger and fencing token. The intent namespace is
    `/registry/ktpu.io/bindintents/<tenant>/<sched>/…` (`tenant_ledger`),
    so one tenant's crash replay or fenced takeover cannot touch another
    tenant's binds; `recover()` replays each tenant's ledger through its
    own Scheduler, PR 4's machinery instantiated per tenant.

A `tick()` is the fleet analog of `Scheduler.schedule_pending`: pump every
tenant's queue, pop per-tenant batches, snapshot each tenant at the SHARED
fleet bucket (fleet/tables.py `fleet_dims` — state/cache.py grows every
tenant up to the union), refresh the resident stack (donated per-tenant
row patches), then ONE vmap'd dispatch with the DRF clamp in-graph
(fleet/cycle.py), and finally the per-tenant commit loops — intent write →
assume → fenced bind → retire, through each tenant's own Scheduler.

Chaos: the `tenant.storm@<tenant>` seam (utils/faultline.py) simulates a
per-tenant watch storm — that tenant's snapshot is invalidated (full
re-encode next tick) and its batch requeues promptly; only ITS CycleStats
degrade, which the chaos suite asserts from metrics, not logs.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..sched.scheduler import CycleStats, Scheduler
from ..state.dims import Dims
from ..utils import faultline
from .cycle import dispatch_fleet, fleet_signature
from .quota import violation_headroom
from .tables import FleetStack, fleet_dims


class _TenantIngest:
    """The v1-dict → typed conversion shim between one tenant's mux routes
    and its Scheduler — the per-tenant half of SchedulerServer's event
    handlers (eventhandlers.go), minus everything the fleet owns."""

    def __init__(self, tenant: "FleetTenant"):
        # imports resolved ONCE here, not per event: these handlers sit on
        # the storm-rate ingest hot path (10k ev/s across the routes), and
        # a function-local import is a sys.modules lookup per call
        from ..api.v1 import node_from_v1, pod_from_v1
        from ..machinery import meta
        from ..sched.server import apply_pod_update_v1, pod_schedulable_v1

        self.tenant = tenant
        self._seq = 0
        self._pod_from_v1 = pod_from_v1
        self._node_from_v1 = node_from_v1
        self._meta_name = meta.name
        self._pod_schedulable_v1 = pod_schedulable_v1
        self._apply_pod_update_v1 = apply_pod_update_v1

    def _to_pod(self, obj):
        p = self._pod_from_v1(obj)
        self._seq += 1
        p.creation_index = self._seq
        return p

    # every handler holds the tenant's ingest lock — the per-tenant
    # "event handlers vs waves" serialization SchedulerServer._mu provides
    # for the single-cluster path (multi-step cache/queue transitions must
    # not interleave with the tick's pop/commit on the same tenant)

    def on_pod_add(self, obj) -> None:
        if self._pod_schedulable_v1(obj):
            with self.tenant.ingest_mu:
                self.tenant.on_pod_add(self._to_pod(obj))

    def on_pod_update(self, old, new) -> None:
        # the SAME transition logic as SchedulerServer's informer handler
        # (sched/server.apply_pod_update_v1) — one definition, two ingest
        # paths that cannot drift
        with self.tenant.ingest_mu:
            self._apply_pod_update_v1(self.tenant.sched, old, new,
                                      self._to_pod)

    def on_pod_delete(self, obj) -> None:
        with self.tenant.ingest_mu:
            self.tenant.on_pod_delete(self._pod_from_v1(obj))

    def on_node_add(self, obj) -> None:
        with self.tenant.ingest_mu:
            self.tenant.on_node_add(self._node_from_v1(obj))

    def on_node_update(self, old, new) -> None:
        with self.tenant.ingest_mu:
            self.tenant.on_node_update(self._node_from_v1(new))

    def on_node_delete(self, obj) -> None:
        with self.tenant.ingest_mu:
            self.tenant.on_node_delete(self._meta_name(obj))


class FleetWatchPlane:
    """ISSUE 13: ONE multiplexed watch stream per resource for the whole
    fleet. Two `WatchMux`es (pods, nodes) each own a single bookmark-
    resumable SharedInformer; every tenant gets a bounded route keyed by
    the tenant label. K tenants therefore put exactly 2 watch streams on
    the apiserver — not 2×K — and a disruption costs at most one resume
    (or, beneath the compaction floor, ONE relist) fleet-wide.

    A mux-stream death does not drop ticks: tenants keep scheduling from
    cached state while `tenant_staleness_seconds` grows; `maintain()`
    (called from FleetServer.tick) narrates the death, revives the stream
    (restart-as-resume), and the staleness decays back to ~0."""

    def __init__(self, server: "FleetServer", client,
                 tenant_label: Optional[str] = None, namespace: str = "",
                 buffer: int = 4096, auto_revive: bool = True):
        from ..client.informers import SharedInformer
        from ..client.watchmux import TENANT_LABEL, WatchMux

        self.server = server
        self.client = client
        self.tenant_label = tenant_label or TENANT_LABEL
        self.auto_revive = auto_revive
        self.pod_mux = WatchMux(
            SharedInformer(client.pods, namespace=namespace),
            tenant_label=self.tenant_label, buffer=buffer, name="pods")
        self.node_mux = WatchMux(
            SharedInformer(client.nodes),
            tenant_label=self.tenant_label, buffer=buffer, name="nodes")
        self._ingests: Dict[str, _TenantIngest] = {}
        self.mux_failovers = 0       # deaths maintain() recovered from
        self.max_staleness = 0.0     # worst staleness ever exported
        self._dead_noted: set = set()  # mux_die narration latch (edge-
        self._started = False          # triggered, not per-tick spam)

    @property
    def muxes(self):
        return (self.pod_mux, self.node_mux)

    def add_route(self, tenant: "FleetTenant") -> None:
        ing = _TenantIngest(tenant)
        self._ingests[tenant.name] = ing
        self.pod_mux.route(tenant.name, on_add=ing.on_pod_add,
                           on_update=ing.on_pod_update,
                           on_delete=ing.on_pod_delete)
        self.node_mux.route(tenant.name, on_add=ing.on_node_add,
                            on_update=ing.on_node_update,
                            on_delete=ing.on_node_delete)

    def start(self) -> "FleetWatchPlane":
        for t in self.server.tenants.values():
            if t.name not in self._ingests:
                self.add_route(t)
        for m in self.muxes:
            m.start()
        for m in self.muxes:
            if not m.wait_for_sync(30.0):
                # a sync timeout must not read as a healthy start: the
                # fleet would tick against empty tenant caches with
                # nothing distinguishing that from a quiet cluster —
                # narrate it (flight-recorder visible, same channel as
                # mux_die) and let staleness carry the ongoing signal
                self.server.telemetry.note_supervisor_event(
                    "mux_unsynced",
                    f"{m.name}: initial list+watch did not sync within "
                    "30s; ticking against unsynced caches until it does")
        self._started = True
        return self

    def stop(self) -> None:
        # a deliberate stop must not read as a death: maintain() guards on
        # _started, so clearing it keeps the next tick from auto-reviving
        # muxes whose route drain threads have already exited (events
        # would flow upstream into silently no-op'ing routes — staleness
        # ~0 while every tenant cache is frozen)
        self._started = False
        for m in self.muxes:
            m.stop()
        if self.server.watch_plane is self:
            # make attach_watch_plane's "stop() it first" instruction
            # actually work: a stopped plane detaches itself
            self.server.watch_plane = None

    def staleness(self) -> float:
        """Seconds since the LEAST-recently-heard-from upstream stream —
        bookmarks count, so a healthy quiet fleet sits near the bookmark
        interval's remainder, never growing."""
        now = time.monotonic()
        return max(0.0, now - min(m.last_signal for m in self.muxes))

    def tenant_staleness(self) -> Dict[str, float]:
        """Per-tenant staleness: the upstream-stream staleness, PLUS a
        route-local penalty for any tenant whose route still has
        undelivered backlog (a stalled consumer is behind even when the
        upstream is live — its serving state is only as fresh as the last
        event it actually applied)."""
        now = time.monotonic()
        fleet = self.staleness()
        out: Dict[str, float] = {}
        # snapshot: a late add_tenant() -> add_route() inserts into
        # _ingests from another thread mid-tick; iterating the live dict
        # would RuntimeError out of the fleet tick
        for name in list(self._ingests):
            stale = fleet
            for m in self.muxes:
                r = m.routes.get(name)
                if r is not None and r.depth() > 0:
                    stale = max(stale, now - r.last_event)
            out[name] = max(0.0, stale)
        return out

    def maintain(self) -> float:
        """Per-tick upkeep: export staleness, revive dead streams. Returns
        the worst staleness exported (pre-revive, so the tick that
        discovers a death records how stale its serving state actually
        was)."""
        from ..sched.metrics import observe_tenant_staleness

        if not self._started:
            return 0.0
        per_tenant = self.tenant_staleness()
        stale = max(per_tenant.values(), default=self.staleness())
        self.max_staleness = max(self.max_staleness, stale)
        observe_tenant_staleness(per_tenant)
        for m in self.muxes:
            if not m.alive:
                # edge-triggered narration: with auto_revive=False a dead
                # stream stays dead across ticks, and a per-tick mux_die
                # would flood every wave record with duplicates — the
                # staleness gauge carries the ongoing signal, the event
                # marks the death
                if m.name not in self._dead_noted:
                    self._dead_noted.add(m.name)
                    self.server.telemetry.note_supervisor_event(
                        "mux_die", f"{m.name}: stream dead, serving cached "
                        f"state ({stale:.1f}s stale)")
                if self.auto_revive:
                    try:
                        m.revive()
                    except RuntimeError as e:
                        # a wedged informer thread (start()'s bounded
                        # re-join expired) must not turn into a fleet-wide
                        # tick exception — "ticks are never dropped for a
                        # watch outage": narrate, keep serving cached
                        # state, retry the revive next tick
                        self.server.telemetry.note_supervisor_event(
                            "mux_revive_failed", f"{m.name}: {e}")
                        continue
                    self.mux_failovers += 1
                    self._dead_noted.discard(m.name)
                    self.server.telemetry.note_supervisor_event(
                        "mux_revive",
                        f"{m.name}: resumed (relists={m.informer.relists}, "
                        f"resumes={m.informer.resumes})")
            else:
                self._dead_noted.discard(m.name)
        return stale

    def stats(self) -> Dict[str, object]:
        return {
            "upstream_watches_per_resource": 1,
            "mux_failovers": self.mux_failovers,
            "max_staleness_seconds": round(self.max_staleness, 3),
            "pods": self.pod_mux.stats(),
            "nodes": self.node_mux.stats(),
        }


def tenant_ledger(storage, tenant: str,
                  scheduler_name: str = "default-scheduler"):
    """A per-tenant BindIntentLedger: intents live under
    `/registry/ktpu.io/bindintents/<tenant>/<scheduler>/…` — disjoint
    prefixes per tenant, so replay/unretired listings are tenant-scoped by
    construction and a takeover of one tenant never reads (or retires)
    another's records."""
    from ..sched.ledger import BindIntentLedger

    return BindIntentLedger(storage,
                            scheduler_name=f"{tenant}/{scheduler_name}")


class FleetTenant:
    """One virtual cluster: a full Scheduler whose DISPATCH the fleet owns.
    The wrapped Scheduler contributes its cache/queue/encoder, the commit
    stage (`commit_wave`: intent write, Bindings, retire), intent replay
    (`recover`) and the event handlers — everything except the device
    cycle, which `FleetServer.tick` runs stacked."""

    def __init__(self, name: str, binder, quota: float = 1.0,
                 ledger=None, fence_source=None,
                 clock: Callable[[], float] = time.monotonic):
        self.name = name
        self.quota = float(quota)
        # mesh=0 pins single-device state: fleet residency/sharding happens
        # at the STACK level (fleet/tables.py), never per tenant
        self.sched = Scheduler(binder=binder, ledger=ledger,
                               fence_source=fence_source, mesh=0,
                               clock=clock)
        # the fleet's prewarmer owns compile-ahead; the per-tenant one
        # would warm single-cluster programs nobody dispatches
        self.sched.prewarmer.enabled = False
        if self.sched.governor is not None:
            # per-tenant governor series label by TENANT, not the shared
            # scheduler name — every tenant writes the same registry, and
            # tenant B's NORMAL must not overwrite A's live brownout
            self.sched.governor.name = name
            self.sched.governor.breaker.name = name
        self.storm_ticks = 0
        # serializes THIS tenant's event ingest (watch-plane route threads)
        # against the tick's mutating phases on the same tenant — the
        # per-tenant analog of SchedulerServer._mu ("event handlers vs
        # waves"): multi-step cache/queue transitions on either side must
        # not interleave. One lock per tenant, so ingest for tenant A never
        # stalls behind tenant B's commit loop.
        self.ingest_mu = threading.Lock()

    # -- event-ingest passthrough (the informer routing surface) -- #

    def on_pod_add(self, pod):
        self.sched.on_pod_add(pod)

    def on_pod_update(self, old, new):
        self.sched.on_pod_update(old, new)

    def on_pod_delete(self, pod):
        self.sched.on_pod_delete(pod)

    def on_node_add(self, node):
        self.sched.on_node_add(node)

    def on_node_update(self, node):
        self.sched.on_node_update(node)

    def on_node_delete(self, name):
        self.sched.on_node_delete(name)


@dataclass
class FleetTickStats:
    """One tick's outcome, per tenant plus the fleet-wide invariants the
    bench budgets enforce."""

    per_tenant: Dict[str, CycleStats] = field(default_factory=dict)
    dispatches: int = 0               # XLA dispatches this tick (budget:
                                      # ONE stacked dispatch, plus one per
                                      # solo-routed tenant)
    drf_violations: int = 0           # tenants whose admitted demand broke
                                      # their headroom (budget: 0)
    drf_clamped: int = 0              # pods deferred by the quota pre-mask
                                      # (per-tenant attribution lives on
                                      # CycleStats.drf_clamped → the
                                      # tenant-labelled DRF_CLAMPED metric)
    cross_tenant_placements: int = 0  # placements onto a node row outside
                                      # the tenant's own cluster (budget: 0)
    tick_seconds: float = 0.0
    staleness_seconds: float = 0.0    # watch-plane staleness at tick start
                                      # (0.0 when no watch plane attached)

    @property
    def scheduled(self) -> int:
        return sum(s.scheduled for s in self.per_tenant.values())

    @property
    def attempted(self) -> int:
        return sum(s.attempted for s in self.per_tenant.values())


class FleetServer:
    """One resident scheduler serving K virtual tenant clusters per vmap'd
    tick. See the module docstring for the ownership model."""

    def __init__(self, batch_size: int = 1024,
                 base_dims: Optional[Dims] = None, mesh=None,
                 node_shards: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 scheduler_name: str = "default-scheduler",
                 storage=None):
        from ..sched.prewarm import BucketPrewarmer
        from ..sched.supervisor import DispatchSupervisor
        from ..utils.envparse import env_int
        from ..utils.platform import enable_compile_cache, steady_heap

        enable_compile_cache()  # before the first tick compiles
        steady_heap()  # before the first tick commits
        self.batch_size = batch_size
        self.clock = clock
        self.scheduler_name = scheduler_name
        self.storage = storage
        if node_shards is None:
            node_shards = env_int("KTPU_FLEET_NODE_SHARDS", 1, 1, 64)
        self.node_shards = int(node_shards)
        self.mesh, self.mesh_state = self._make_fleet_mesh(
            mesh, self.node_shards)
        self.prewarmer = BucketPrewarmer()
        self.supervisor = DispatchSupervisor(prewarmer=self.prewarmer,
                                             mesh_state=self.mesh_state)
        self.prewarmer.supervisor = self.supervisor
        # fleet-level flight recorder (sched/telemetry.py): per-tick phase
        # spans + per-TENANT stats on each record; storms and abandoned
        # dispatches auto-dump. Per-pod e2e latency stays per tenant (each
        # FleetTenant's Scheduler owns its tracker/commit path).
        from ..sched.telemetry import SchedulerTelemetry

        self.telemetry = SchedulerTelemetry(name="fleet")
        self.supervisor.event_sink = self.telemetry.note_supervisor_event
        self.supervisor.wave_seq = self.telemetry.recorder.next_seq
        # the resident stacked tables every tick's dispatch runs on
        self.stack = FleetStack(mesh=self.mesh)
        self._fleet_dims: Dims = replace(base_dims or Dims(),
                                         has_node_name=False)
        self.tenants: Dict[str, FleetTenant] = {}
        # cumulative fleet-wide invariants (bench reads these)
        self.ticks = 0
        self.total_drf_violations = 0
        self.total_cross_tenant = 0
        self.total_drf_clamped = 0
        self.max_dispatches_per_tick = 0
        self._super_epoch = self._supervisor_epoch()
        # re-admission rewarm must target the FLEET mesh's executable key.
        # With a fleet-mode MeshState attached the supervisor reforms the
        # (possibly 2-D) fleet mesh itself — the degrade→reform ladder under
        # the 2-D signature; the provider remains the fallback for an
        # adopted raw Mesh object (no MeshState to reform).
        self.supervisor.mesh_provider = lambda: self.mesh
        # ISSUE 13: the shared watch plane (attach_watch_plane) — one
        # multiplexed, bookmark-resumable stream per resource for all K
        # tenants, maintained (staleness export + dead-stream revive)
        # from every tick
        self.watch_plane: Optional[FleetWatchPlane] = None

    def _supervisor_epoch(self):
        """Changes whenever a primary dispatch hung/failed or the backend
        was re-admitted — i.e. whenever a zombie worker might still hold
        the resident stacked buffers."""
        st = self.supervisor.stats
        return (st.degraded_cycles, st.abandoned, st.recoveries)

    @staticmethod
    def _make_fleet_mesh(mesh, node_shards: int = 1):
        """→ (mesh, mesh_state). An int/str request builds a fleet-mode
        MeshState (pow2 width, the degrade→reform ladder owns the mesh from
        then on); a raw Mesh object is adopted as-is with no state to
        reform. Garbage values clamp to "no mesh" — single-device serving —
        instead of crashing int()."""
        if mesh is None or mesh == 0:
            return None, None
        from jax.sharding import Mesh

        from ..parallel.mesh import MeshState
        from ..utils.envparse import clamped_int

        if isinstance(mesh, Mesh):
            return mesh, None
        n = clamped_int(mesh, 0, 0, 4096)
        if n <= 1:
            return None, None
        ns = clamped_int(node_shards, 1, 1, 64)
        state = MeshState(n, fleet_node_shards=ns)
        if state.mesh is None:
            return None, None
        return state.mesh, state

    def _node_shard_width(self) -> int:
        if self.mesh is None:
            return 1
        from ..parallel.mesh import fleet_mesh_shape

        return fleet_mesh_shape(self.mesh)[1]

    def _sync_mesh(self) -> None:
        """Adopt the MeshState's current mesh (degrade dropped it; reform
        rebuilt it — possibly narrower, always a FRESH object). The stack
        re-homes and full-restacks onto the new placement."""
        if self.mesh_state is None or self.mesh_state.mesh is self.mesh:
            return
        self.mesh = self.mesh_state.mesh
        self.stack.mesh = self.mesh
        self.stack.invalidate()

    # ------------------------------------------------------------------ #
    # tenant lifecycle
    # ------------------------------------------------------------------ #

    def add_tenant(self, name: str, binder=None, quota: float = 1.0,
                   ledger=None, fence_source=None) -> FleetTenant:
        if name in self.tenants:
            raise ValueError(f"tenant {name!r} already registered")
        if binder is None:
            from ..sched.scheduler import RecordingBinder

            binder = RecordingBinder()
        if ledger is None and self.storage is not None:
            ledger = tenant_ledger(self.storage, name, self.scheduler_name)
        t = FleetTenant(name, binder, quota=quota, ledger=ledger,
                        fence_source=fence_source, clock=self.clock)
        self.tenants[name] = t
        if self.watch_plane is not None:
            # a late tenant joins the EXISTING streams: its routes resync
            # from the mux indexers — the apiserver sees no new watch
            self.watch_plane.add_route(t)
        return t

    def tenant(self, name: str) -> FleetTenant:
        return self.tenants[name]

    def attach_watch_plane(self, client, tenant_label: Optional[str] = None,
                           namespace: str = "", buffer: int = 4096,
                           auto_revive: bool = True,
                           start: bool = True) -> FleetWatchPlane:
        """Wire the fleet to a live apiserver through ONE multiplexed watch
        stream per resource (ISSUE 13). Registers a route per existing
        tenant; tenants added later join the same streams."""
        if self.watch_plane is not None:
            # silently replacing a live plane would leave the old one's
            # informer + route threads running — double ingest per event
            # and 2 leaked upstream streams, the exact amplification this
            # subsystem exists to kill
            raise ValueError("a watch plane is already attached; stop() "
                             "it first")
        self.watch_plane = FleetWatchPlane(
            self, client, tenant_label=tenant_label, namespace=namespace,
            buffer=buffer, auto_revive=auto_revive)
        if start:
            self.watch_plane.start()
        return self.watch_plane

    def recover(self, now: Optional[float] = None) -> Dict[str, object]:
        """Startup/takeover reconciliation, per tenant through its OWN
        ledger namespace — tenant A's replay can complete/release only
        entries under A's prefix; B's intents are not even listed."""
        out = {}
        for name, t in self.tenants.items():
            with t.ingest_mu:
                out[name] = t.sched.recover(now=now)
        return out

    # ------------------------------------------------------------------ #
    # the fleet tick
    # ------------------------------------------------------------------ #

    def _snapshot_round(self, tlist, batches):
        """Snapshot every tenant at the shared fleet bucket, growing the
        bucket (and re-snapshotting) until all tenants agree — convergence
        is ≤2 passes in practice (one tenant grew, everyone follows)."""
        from ..sched.cycle import snapshot_with_keys

        snaps: Dict[str, object] = {}
        keys: Dict[str, Tuple] = {}
        kn = self._node_shard_width()
        for _ in range(4):
            for t in tlist:
                pending = [p for p, _ in batches[t.name]]
                snaps[t.name], keys[t.name] = snapshot_with_keys(
                    t.sched.cache, t.sched.encoder, pending,
                    self._fleet_dims,
                    device=self.supervisor.snapshot_device())
            union = fleet_dims([snaps[t.name].dims for t in tlist],
                               base=self._fleet_dims)
            if kn > 1:
                # 2-D mesh: the bucket's node axis must divide the
                # node-shard row so the stacked [K, N, …] planes shard
                # without padding. grown_for keeps N pow2 (≤256) or a
                # ≥32-multiple above, so a pow2 row width makes this a
                # no-op in the steady state; the guard covers raw shapes.
                from ..parallel.mesh import padded_node_count

                union = replace(union, N=padded_node_count(union.N, kn))
            if all(replace(snaps[t.name].dims, has_node_name=False)
                   == union for t in tlist):
                self._fleet_dims = union
                return snaps, keys
            self._fleet_dims = union
        raise RuntimeError("fleet bucket did not converge in 4 passes")

    def micro_pass(self, now: Optional[float] = None,
                   tick: Optional[FleetTickStats] = None
                   ) -> Dict[str, CycleStats]:
        """Streaming micro-admission across the fleet (ISSUE 18): each
        micro-ready tenant admits its fresh-delta lane through ITS OWN
        scheduler — own snapshot, own governor/breaker, own ledger
        namespace — under its ingest lock, so per-tenant isolation is
        structural, not asserted. Tenants with mixed/deep/empty backlogs
        are untouched; those pods ride the stacked bulk dispatch.

        Rides the top of every tick; a server loop may ALSO call it
        between ticks for sub-tick admission latency. When `tick` is
        given, each tenant's micro outcome is merged into its per-tenant
        stats so the tenant-labelled metrics (TENANT_ADMITTED et al.)
        and the flight-recorder fleet record count streamed admissions."""
        now = self.clock() if now is None else now
        out: Dict[str, CycleStats] = {}
        for t in list(self.tenants.values()):
            if not t.sched.microwave:
                continue
            with t.ingest_mu:
                st = t.sched.schedule_micro(now)
            if not st.micro:
                continue
            out[t.name] = st
            agg = tick.per_tenant.get(t.name) if tick is not None else None
            if agg is not None:
                agg.attempted += st.attempted
                agg.scheduled += st.scheduled
                agg.unschedulable += st.unschedulable
                agg.bind_errors += st.bind_errors
                agg.aborted += st.aborted
                agg.requeued += st.requeued
                agg.shed += st.shed
                agg.micro += st.micro
                agg.assignments.update(st.assignments)
                agg.failed_keys.extend(st.failed_keys)
        return out

    def tick(self, now: Optional[float] = None) -> FleetTickStats:
        now = self.clock() if now is None else now
        t0 = time.perf_counter()
        tick = FleetTickStats()
        tlist = list(self.tenants.values())
        if not tlist:
            return tick
        for t in tlist:
            tick.per_tenant[t.name] = CycleStats()
        span = self.telemetry.wave_span("fleet-tick")
        # streaming micro-admission interleave (ISSUE 18) before the
        # stacked bulk dispatch — no-op for every tenant unless its
        # scheduler opted in (KTPU_MICROWAVE) and its lane is micro-ready
        if self.micro_pass(now, tick=tick):
            span.mark("micro")
        if self.watch_plane is not None:
            # watch-plane upkeep rides the tick: staleness export first
            # (a dead stream's tick records HOW stale it served), then the
            # dead-stream revive — ticks are never dropped for a watch
            # outage, they degrade to cached state with a visible metric
            tick.staleness_seconds = self.watch_plane.maintain()

        # ---- pump + storm seam + governed pop ---- #
        # each tenant's pop phase holds ITS ingest lock (handlers-vs-waves,
        # per tenant): a route thread's multi-step transition can't
        # interleave with the pump/pop on the same tenant's queue
        batches: Dict[str, List] = {}
        for t in tlist:
            with t.ingest_mu:
                s = t.sched
                st = tick.per_tenant[t.name]
                s.queue.pump(now)
                s.cache.cleanup(now)
                if faultline.should("tenant.storm", t.name):
                    # injected per-tenant watch storm: the tenant's resident
                    # encoding is no longer trusted (full re-encode next tick)
                    # and this tick admits nothing for it — purely ITS
                    # degradation, the other tenants' rows are untouched. The
                    # "storm" event makes this a flight-recorder dump trigger:
                    # the degraded tick is explainable from the artifact.
                    t.storm_ticks += 1
                    st.degraded += 1
                    self.telemetry.note_supervisor_event("storm", t.name)
                    s.cache.invalidate_snapshot()
                    batches[t.name] = []
                    continue
                # per-TENANT overload governor (sched/overload.py): one
                # tenant's storm sheds/pauses only that tenant — composing
                # with the DRF clamp, which bounds a tenant's SHARE while the
                # governor bounds the control plane's own burn for it
                gov = s.governor
                decision = None
                pop_limit = self.batch_size
                if gov is not None:
                    decision = gov.begin_wave(now, s.queue.depths())
                    if decision.release_deferred:
                        released = s.queue.release_deferred(now)
                        if released:
                            self.telemetry.note_supervisor_event(
                                "deferred_release",
                                f"{t.name}: {released} pods re-admitted")
                    if not decision.dispatch_allowed:
                        st.commit_paused += 1
                        batches[t.name] = []
                        continue
                    if decision.wave_limit:
                        pop_limit = min(pop_limit, decision.wave_limit)
                batch = s.queue.pop_batch(pop_limit, now=now)
                if decision is not None and decision.shed_below is not None \
                        and batch:
                    kept = []
                    shed_n = 0
                    for pod, attempts in batch:
                        if pod.priority < decision.shed_below \
                                and s.queue.park_deferred(pod, attempts,
                                                          now=now):
                            shed_n += 1
                        else:
                            kept.append((pod, attempts))
                    batch = kept
                    if shed_n:
                        st.shed += shed_n
                        gov.note_shed(shed_n)
                batches[t.name] = batch
                # += : a micro_pass admission above already counted here
                st.attempted += len(batch)
        span.mark("pump")

        from ..sched.supervisor import DispatchAbandonedError
        from ..sched.telemetry import xla_scope

        # batches are popped: from here to the dispatch result, EVERY
        # failure path must hand them back to their queues — losing them
        # is the one thing a scheduler may never do
        try:
            # what the tick compiles on this thread (the snapshot round, the
            # stack's refresh) is the XLA account's under `tick`
            with xla_scope("tick", on_path=True,
                           seq=self.telemetry.recorder.next_seq(),
                           sink=self.telemetry.note_supervisor_event):
                out, exp, snaps = self._dispatch_tick(tlist, batches, tick,
                                                      now, span)
        except DispatchAbandonedError:
            # the abandoned worker's zombie thread may still hold (or be
            # executing on) the resident stacked buffers — never donate or
            # scatter onto them again; the next healthy tick full-restacks.
            # Every popped pod goes back to its queue.
            self.stack.invalidate()
            self._requeue_batches(tlist, batches, tick, now)
            span.mark("requeue")
            tick.tick_seconds = time.perf_counter() - t0
            self._finish_tick(tick, span)
            return tick
        except Exception:
            # any other post-pop failure (bucket non-convergence, a
            # donation assert in the stack refresh, an unexpected dispatch
            # error): requeue everything, drop the possibly half-patched
            # stack, and re-raise for visibility
            self.stack.invalidate()
            self._requeue_batches(tlist, batches, tick, now)
            span.mark("requeue")
            tick.tick_seconds = time.perf_counter() - t0
            self._finish_tick(tick, span)
            raise

        self._commit_tick(tlist, out, exp, batches, snaps, tick, now)
        span.mark("bind-commit")
        tick.tick_seconds = time.perf_counter() - t0
        # per-tenant governor feedback: the shared tick's wall time is
        # every tenant's deadline signal (commit outcomes already fed the
        # breakers from each tenant's own _commit)
        for t in tlist:
            if t.sched.governor is not None:
                t.sched.governor.end_wave(
                    now, tick.per_tenant[t.name].attempted,
                    tick.tick_seconds)
        self._finish_tick(tick, span)
        return tick

    @staticmethod
    def _pad_quota(tlist, width: int) -> List[float]:
        """Pad tenants carry quota 0.0: with zero capacity their share and
        demand are zero, so they can neither admit nor flag — the ONE
        definition every consumer (primary dispatch, fallback re-encode,
        violation check) must agree on."""
        return [t.quota for t in tlist] + [0.0] * (width - len(tlist))

    @staticmethod
    def _requeue_batches(tlist, batches, tick, now) -> None:
        """Hand every still-unconsumed popped batch back to its tenant's
        queue (prompt retry, no failure verdict) — solo-routed and stormed
        tenants' batches are already empty lists here."""
        for t in tlist:
            with t.ingest_mu:
                st = tick.per_tenant[t.name]
                for pod, attempts in batches[t.name]:
                    st.aborted += 1
                    st.requeued += 1
                    t.sched.queue.add_prompt_retry(pod, attempts=attempts,
                                                   now=now)

    def _dispatch_tick(self, tlist, batches, tick, now, span):
        """Everything between the batch pop and the device results: the
        snapshot convergence round, solo routing, the resident stack's
        refresh and ONE vmap'd dispatch. Returns `(out, exp, snaps)` for
        _commit_tick. Raises propagate to tick()'s requeue guard — this
        method never loses a popped pod."""
        # adopt a reformed/dropped mesh BEFORE snapshotting: the bucket's
        # node-shard divisibility and the stack's placement follow it
        self._sync_mesh()
        snaps, keys = self._snapshot_round(tlist, batches)
        span.mark("snapshot")

        # ---- tenants the vmap cannot express run their own single-
        # cluster wave (counted as extra dispatches; the fleet budget
        # shape carries neither): gang-bearing batches (group-atomic
        # admission needs host rejection rounds) and nodeName-pinned
        # batches (routing one tenant's pin through the shared program
        # would downgrade EVERY tenant to the sequential scan engine —
        # exactly the cross-tenant interference the fleet forbids) ---- #
        solo_ran = False
        for t in tlist:
            # the solo wave is this tenant's whole cycle, held under its
            # ingest lock exactly like SchedulerServer.run_one_wave holds
            # _mu across schedule_pending — a route handler's multi-step
            # cache/queue transition must not interleave with the wave's
            # own mutations. Known tradeoff: the tenant's mux route keeps
            # buffering meanwhile, so a wave longer than buffer/event-rate
            # costs that route a bounded, route-local resync (never an
            # apiserver relist); size `buffer` for the worst solo wave.
            with t.ingest_mu:
                needs_solo = (snaps[t.name].gang is not None
                              or snaps[t.name].dims.has_node_name)
                if not needs_solo or not batches[t.name]:
                    continue
                s = t.sched
                for pod, attempts in batches[t.name]:
                    # attempts-1: the fleet pop and the solo wave's own pop are
                    # ONE real attempt — re-adding the post-pop count would let
                    # the solo pop double-increment and escalate a failing
                    # pod's backoff 4x per failure instead of 2x
                    s.queue.add_prompt_retry(pod, attempts=attempts - 1,
                                             now=now)
                solo = s.schedule_pending(now)
                st = tick.per_tenant[t.name]
                st.scheduled += solo.scheduled
                st.unschedulable += solo.unschedulable
                st.bind_errors += solo.bind_errors
                # aborted/requeued/failed_keys carry through too: a chaos-
                # injected abandonment inside the solo wave must show up in
                # THIS tenant's fleet counters (the chaos suite asserts
                # isolation from these, not from logs)
                st.aborted += solo.aborted
                st.requeued += solo.requeued
                st.failed_keys.extend(solo.failed_keys)
                st.assignments.update(solo.assignments)
                tick.dispatches += 1
                batches[t.name] = []
                solo_ran = True
        if solo_ran:
            # the solo waves consumed those batches, mutated their tenants'
            # caches, and may have grown the fleet bucket — re-snapshot
            # EVERY tenant so the whole stack agrees on the converged
            # bucket (unchanged tenants hit their cache's snapshot path; a
            # per-solo-tenant refresh would leave the others at the old
            # shapes and crash the restack with the batches already popped)
            snaps, keys = self._snapshot_round(tlist, batches)
            span.mark("solo")

        # no waves→scan downgrade here: nodeName-bearing batches were solo-
        # routed above, so every snapshot entering the shared program has
        # has_node_name=False (re-snapshotted with an empty batch) — one
        # tenant's pin must never serialize the other K-1 tenants
        engine = "waves"
        d = self._fleet_dims
        if self.supervisor.healthy:
            epoch = self._supervisor_epoch()
            if epoch != self._super_epoch:
                # the primary hung/failed or the backend was re-admitted
                # since the stack's last refresh: a hung dispatch's
                # abandoned worker may STILL hold the resident buffers
                # (handle.result() returned the fallback's answer without
                # raising), and a sub-second probe can re-admit before the
                # next tick — donating those buffers would alias them out
                # from under the wedged execution. Full-restack fresh
                # instead (the fleet analog of the cache's
                # _dispatch_inflight copy gate).
                self.stack.invalidate()
                self._super_epoch = epoch

        # ---- resident stack refresh (donated per-tenant row patches) --- #
        stack = self.stack
        if self.supervisor.healthy:
            Kp = stack.refresh([snaps[t.name] for t in tlist],
                               [keys[t.name] for t in tlist], d)
        else:
            # degraded: the resident buffers live on the lost backend —
            # scattering onto them would dispatch onto dead hardware before
            # the supervisor's ladder even runs. Drop the stack (fresh
            # full restack on re-admission) and let the fallback re-encode
            # from host staging; submit() skips the primary while unhealthy.
            stack.invalidate()
            Kp = stack.padded_k(len(tlist))
        span.mark("stack-refresh")
        quota = jnp.asarray(self._pad_quota(tlist, Kp), jnp.float32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.mesh import TENANT_AXIS

            quota = jax.device_put(
                quota, NamedSharding(self.mesh, PartitionSpec(TENANT_AXIS)))

        # ---- compile-ahead + supervisor bookkeeping under the FLEET key - #
        fsig = fleet_signature(Kp)
        self.prewarmer.observe(
            d, n_nodes=max(t.sched.cache.node_count for t in tlist),
            n_existing=max(t.sched.cache.pod_count for t in tlist),
            engine=engine, mesh=self.mesh, fleet=fsig)
        self.prewarmer.ensure_warm(d, engine, mesh=self.mesh, fleet=fsig)
        self.supervisor.note_cycle_signature(d, engine, (), False,
                                             fleet=fsig)
        span.mark("prewarm")

        # ---- ONE vmap'd dispatch ---- #
        # decision provenance (ISSUE 10): one flag for the whole stack —
        # tenants share the process env, and the vmap'd program is one
        # executable. Attribution fans back out per tenant in _commit_tick.
        explain_on = any(t.sched.explainer is not None for t in tlist)

        def _primary():
            if stack.block is None:
                # the stack was invalidated AFTER the healthy check above
                # (tick started degraded, or the background prober
                # re-admitted the backend between that check and submit —
                # _readmit flips health asynchronously): full-restack from
                # THIS tick's snapshots instead of dereferencing the
                # dropped buffers
                stack.refresh([snaps[t.name] for t in tlist],
                              [keys[t.name] for t in tlist], d)
            out = dispatch_fleet(stack.tables, stack.pending, stack.keys,
                                 d.D, stack.existing, engine, quota,
                                 dims=d, prewarmer=self.prewarmer,
                                 mesh=self.mesh, explain=explain_on)
            res, exp = out if explain_on else (out, None)
            return jax.device_get(res), \
                (jax.device_get(exp) if exp is not None else None)

        def _fallback(dev, hung=False):
            # degraded fleet tick: re-encode the tenants onto the
            # CPU fallback from host staging (the single-cluster ladder,
            # per tenant) and dispatch the stack there — no resident
            # buffers of the lost backend are touched
            from ..sched.cycle import snapshot_with_keys
            from .tables import stack_blocks

            blocks = []
            for t in tlist:
                sn, ky = snapshot_with_keys(
                    t.sched.cache, t.sched.encoder,
                    [p for p, _ in batches[t.name]], self._fleet_dims,
                    device=dev)
                snaps[t.name] = sn
                blocks.append((sn.tables, sn.pending, sn.existing, ky))
            if Kp > len(blocks):
                from .tables import empty_tenant_block

                blocks.extend([empty_tenant_block(d)] * (Kp - len(blocks)))
            tb, pe, ex, ky = jax.device_put(stack_blocks(blocks), dev)
            q = jax.device_put(jnp.asarray(self._pad_quota(tlist, Kp),
                                           jnp.float32), dev)
            with jax.default_device(dev):
                out = dispatch_fleet(tb, pe, ky, d.D, ex, engine, q,
                                     explain=explain_on)
                res, exp = out if explain_on else (out, None)
                return jax.device_get(res), \
                    (jax.device_get(exp) if exp is not None else None)

        from ..parallel.mesh import mesh_key as _mesh_key

        handle = self.supervisor.submit(
            "cycle",
            (replace(d, has_node_name=False), engine, fsig,
             _mesh_key(self.mesh)),
            _primary, _fallback)
        span.mark("dispatch")
        out, exp = handle.result()
        span.mark("readback")
        tick.dispatches += 1
        return out, exp, snaps

    def _commit_tick(self, tlist, out, exp, batches, snaps, tick,
                     now) -> None:
        """The per-tenant commit loops (PR 4 machinery per tenant): intent
        write → assume → fenced bind → retire, through each tenant's own
        Scheduler, plus the DRF violation check over the dispatch's
        outputs."""
        node = np.asarray(out.node)
        admitted = np.asarray(out.admitted)
        share = np.asarray(out.share)
        dom = np.asarray(out.dom)
        # the DRF invariant the bench budget enforces, checked through the
        # SAME tensor helper the quota tests golden (pad tenants have zero
        # admitted demand and can never flag)
        viol = violation_headroom(
            share, dom, admitted,
            np.asarray(self._pad_quota(tlist, int(share.shape[0])),
                       np.float32), xp=np)
        tick.drf_violations += int(viol[:len(tlist)].sum())
        for k, t in enumerate(tlist):
            with t.ingest_mu:  # commit phase vs this tenant's route threads
                s = t.sched
                st = tick.per_tenant[t.name]
                order = snaps[t.name].node_order
                cycle = s.queue.current_cycle()
                # per-TENANT decision provenance (ISSUE 10): slice tenant k's
                # rows off the stacked attribution and feed ITS explainer —
                # quota-clamped pods (admitted=False) are excluded: they carry
                # no verdict this tick, and their zeroed attribution would
                # render as empty-reason noise
                if exp is not None and s.explainer is not None \
                        and batches[t.name]:
                    idx = [i for i in range(len(batches[t.name]))
                           if admitted[k, i]]
                    if idx:
                        from ..ops.assign import ExplainResult

                        sl = ExplainResult(*(np.asarray(a)[k][idx]
                                             for a in exp))
                        try:
                            rec = s.explainer.observe_wave(
                                [batches[t.name][i] for i in idx],
                                node[k][idx], sl, order, now=now)
                        except Exception:  # noqa: BLE001 - provenance must
                            rec = None     # never take down a tick
                        if rec:
                            self.telemetry.note_supervisor_event(
                                "explain", f"{t.name}: "
                                f"{rec.get('unschedulable', 0)} attributed")
                commits: List[Tuple] = []
                failures: List[Tuple] = []
                for i, (pod, attempts) in enumerate(batches[t.name]):
                    if not admitted[k, i]:
                        # quota-clamped, not unschedulable: the pod is fine,
                        # the tenant's headroom wasn't — defer promptly. The
                        # clamp count rides CycleStats so observe_fleet_tick
                        # emits the tenant-labelled DRF_CLAMPED series.
                        st.requeued += 1
                        st.drf_clamped += 1
                        tick.drf_clamped += 1
                        s.queue.add_prompt_retry(pod, attempts=attempts,
                                                 now=now)
                        continue
                    ni = int(node[k, i])
                    if ni < 0:
                        failures.append((pod, attempts))
                        continue
                    if s.cache.get_pod(pod.key) is not None:
                        continue  # skipPodSchedule (stale queue entry)
                    if ni >= len(order) or not order[ni]:
                        # a placement onto a node row outside this tenant's
                        # own cluster — the inert-row contract broke
                        tick.cross_tenant_placements += 1
                        failures.append((pod, attempts))
                        continue
                    commits.append((pod, order[ni], attempts))
                # the tenant's own Scheduler's commit stage, under ITS
                # breaker (one tenant's opening mid-commit leaves the other
                # tenants' loops untouched). On a fleet tick the commits a
                # failed intent write aborted count as requeued too.
                unwritten = s.commit_wave(commits, now, cycle, st)
                st.requeued += len(unwritten)
                for pod, attempts in failures:
                    st.unschedulable += 1
                    st.failed_keys.append(pod.key)
                    s.queue.add_unschedulable(pod, attempts, now, cycle=cycle)

    def _finish_tick(self, tick: FleetTickStats, span=None) -> None:
        from ..sched.metrics import observe_fleet_tick

        self.ticks += 1
        self.total_drf_violations += tick.drf_violations
        self.total_cross_tenant += tick.cross_tenant_placements
        self.total_drf_clamped += tick.drf_clamped
        self.max_dispatches_per_tick = max(self.max_dispatches_per_tick,
                                           tick.dispatches)
        # per-tenant attribution happens INSIDE observe_fleet_tick now:
        # the chaos suite and bench assert tenant isolation (and the DRF
        # clamp) from the tenant-labelled metrics, routed through
        # CycleStats — never from FleetServer internals
        observe_fleet_tick(tick.per_tenant)
        if span is not None:
            self.telemetry.finish_wave(
                span, engine="fleet", dims=self._fleet_dims,
                fleet={name: {"attempted": st.attempted,
                              "scheduled": st.scheduled,
                              "requeued": st.requeued,
                              "degraded": st.degraded,
                              "drf_clamped": st.drf_clamped,
                              "shed": st.shed,
                              "aborted": st.aborted}
                       for name, st in tick.per_tenant.items()},
                extra={"dispatches": tick.dispatches,
                       "drf_violations": tick.drf_violations,
                       "cross_tenant_placements":
                           tick.cross_tenant_placements})

    def run_until_idle(self, max_ticks: int = 64,
                       stall_ticks: int = 2) -> FleetTickStats:
        """Tick until every tenant's active queue drains, or nothing has
        scheduled for `stall_ticks` consecutive ticks (a quota-clamped
        tenant's deferred pods requeue promptly, so its active queue never
        empties — headroom, not the scheduler, is what it waits on)."""
        total = FleetTickStats()
        for t in self.tenants.values():
            total.per_tenant[t.name] = CycleStats()
        stalled = 0
        for _ in range(max_ticks):
            tk = self.tick()
            stalled = stalled + 1 if tk.scheduled == 0 else 0
            total.dispatches += tk.dispatches
            total.drf_violations += tk.drf_violations
            total.drf_clamped += tk.drf_clamped
            total.cross_tenant_placements += tk.cross_tenant_placements
            total.tick_seconds += tk.tick_seconds
            for name, st in tk.per_tenant.items():
                agg = total.per_tenant[name]
                agg.attempted += st.attempted
                agg.scheduled += st.scheduled
                agg.unschedulable += st.unschedulable
                agg.bind_errors += st.bind_errors
                agg.aborted += st.aborted
                agg.requeued += st.requeued
                agg.degraded += st.degraded
                agg.drf_clamped += st.drf_clamped
                agg.assignments.update(st.assignments)
            if all(t.sched.queue.lengths()[0] == 0
                   for t in self.tenants.values()):
                break
            if stalled >= stall_ticks:
                break
        return total
