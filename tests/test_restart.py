"""Exactly-once binding across scheduler crash/restart (sched/ledger.py).

The kill matrix: for every crash point in the bind lifecycle —

    pre_intent    before the wave's intent record is written
    post_intent   after the intent, before any Binding write
    post_bind     after the Binding writes, before the intent retires
    takeover      mid-reconciliation of a successor

— a restarted (or warm-standby takeover) scheduler must reconcile to the
ledger invariants of test_chaos.py: NO pod lost, NO pod double-bound, and
the generations converge (every intent retired, cache snapshot served from
cache). The fencing half is asserted against the real apiserver: a deposed
leader's stale-token Binding is rejected with 409.

Crash simulation uses `proc.crash@site` (utils/faultline.py crashpoint):
InjectedCrash is a BaseException, so it unwinds through every
`except Exception` guard exactly like SIGKILL — durable state (storage,
the intent ledger, committed Bindings) stays where the kill caught it.
"""

import time

import pytest

from kubernetes_tpu.api.types import (
    DEFAULT_FENCING_LEASE,
    FENCING_LEASE_ANNOTATION,
    FENCING_TOKEN_ANNOTATION,
    Node,
    Pod,
    Resources,
)
from kubernetes_tpu.sched.ledger import BindIntentLedger
from kubernetes_tpu.sched.scheduler import Scheduler
from kubernetes_tpu.state.dims import Dims
from kubernetes_tpu.storage.native import PyKV
from kubernetes_tpu.storage.store import Storage
from kubernetes_tpu.utils import faultline

pytestmark = pytest.mark.chaos

HOSTNAME = "kubernetes.io/hostname"
N_NODES = 4
N_PODS = 12


@pytest.fixture(autouse=True)
def _clean_faultline():
    yield
    faultline.uninstall()


def mknode(name, cpu=4, mem="8Gi", **kw):
    kw.setdefault("labels", {HOSTNAME: name})
    return Node(name=name,
                allocatable=Resources.make(cpu=cpu, memory=mem, pods=110),
                **kw)


def mkpod(name, cpu="100m", mem="64Mi", **kw):
    return Pod(name=name, requests=Resources.make(cpu=cpu, memory=mem), **kw)


class DurableBinder:
    """The Binding registry a crash cannot erase: binds survive process
    death, and — like the real apiserver's already-assigned guard — a
    second bind of the same pod is REFUSED and counted, so a double-bind
    can never hide as an overwrite."""

    def __init__(self):
        self.bound = {}            # pod key → node name
        self.double_bind_attempts = 0
        self.bind_log = []         # every accepted (key, node), in order

    def bind(self, pod, node_name):
        if pod.key in self.bound:
            self.double_bind_attempts += 1
            return False
        self.bound[pod.key] = node_name
        self.bind_log.append((pod.key, node_name))
        return True


class Cluster:
    """One durable 'etcd' (Storage) + Binding registry + informer truth,
    shared by every scheduler incarnation of a drill.

    With ``data_dir`` the store is WAL-backed (ISSUE 19): the APISERVER
    itself can now die in a drill, and ``reboot_storage`` brings up a fresh
    incarnation recovered from disk — in-memory state is lost, the log is
    not."""

    def __init__(self, n_nodes=N_NODES, n_pods=N_PODS, data_dir=None,
                 durability="always", ledger="storage"):
        self.data_dir = data_dir
        self.durability = durability
        # how a scheduler incarnation keeps its ledger (ISSUE 39):
        # "storage" holds the store itself; "api" writes the same records
        # as bindintents.ktpu.io through a client of an apiserver over the
        # store; "api-http" does so across a real socket
        self.ledger_form = ledger
        self.gateway = None
        self.storage = self._open_storage()
        self.binder = DurableBinder()
        self.nodes = [mknode(f"n{i}") for i in range(n_nodes)]
        self.pods = {f"default/p{i}": mkpod(f"p{i}") for i in range(n_pods)}

    def _open_storage(self):
        if self.data_dir is None:
            return Storage(kv=PyKV())
        return Storage(data_dir=self.data_dir, durability=self.durability)

    def reboot_storage(self):
        """The apiserver process is dead: quiesce the corpse's pump thread
        (a real SIGKILL flushes nothing) and recover a new store from the
        WAL on disk."""
        self.storage._stop.set()
        self.storage = self._open_storage()
        return self.storage

    def close(self):
        if self.gateway is not None:
            self.gateway.stop()
        self.storage.close()

    def make_ledger(self):
        """A new incarnation's ledger over the shared store, in the form
        this drill runs."""
        if self.ledger_form == "storage":
            return BindIntentLedger(self.storage)
        from kubernetes_tpu.apiserver import APIServer, HTTPGateway
        from kubernetes_tpu.client import Client
        from kubernetes_tpu.sched.ledger import APIBindIntentLedger

        api = APIServer(storage=self.storage)
        if self.ledger_form == "api":
            return APIBindIntentLedger(Client.local(api))
        if self.gateway is not None:
            self.gateway.stop()
        self.gateway = HTTPGateway(api).start()
        return APIBindIntentLedger(Client.http(self.gateway.url))

    def lookup(self, key):
        """Informer truth: the pod with its COMMITTED node (from the
        durable Binding registry), or None if deleted."""
        pod = self.pods.get(key)
        if pod is None:
            return None
        node = self.binder.bound.get(key, "")
        if node:
            import dataclasses

            return dataclasses.replace(pod, node_name=node)
        return pod

    def boot(self, **kw):
        """One scheduler incarnation: fresh in-memory state (cache, queue,
        encoder), informers replayed from truth, ledger over the shared
        storage. Mirrors a process restart: only storage + Bindings
        persist."""
        kw.setdefault("base_dims", Dims(N=16, P=16, E=64))
        kw.setdefault("batch_size", 8)
        s = Scheduler(binder=self.binder, ledger=self.make_ledger(), **kw)
        for n in self.nodes:
            s.on_node_add(n)
        for key, pod in self.pods.items():
            bound = self.binder.bound.get(key, "")
            if bound:
                import dataclasses

                s.on_pod_add(dataclasses.replace(pod, node_name=bound))
            else:
                s.on_pod_add(pod)
        return s

    def assert_exactly_once(self, s):
        """The restart ledger: every pod bound exactly once, zero refused
        double-binds, no unretired intents, snapshot generation
        converged."""
        assert len(self.binder.bound) == len(self.pods), (
            f"lost pods: {set(self.pods) - set(self.binder.bound)}")
        assert self.binder.double_bind_attempts == 0
        keys = [k for k, _ in self.binder.bind_log]
        assert len(set(keys)) == len(keys), "double-bound pods"
        assert s.ledger.unretired() == [], "unretired intents survived"
        snap1 = s.cache.snapshot(s.encoder, [], s.base_dims)
        snap2 = s.cache.snapshot(s.encoder, [], s.base_dims)
        assert snap2 is snap1 and s.cache.last_snapshot_mode == "cached"
        assert snap1.generation == s.cache.generation


# --------------------------------------------------------------------- #
# the kill matrix
# --------------------------------------------------------------------- #


# every drill below views the ledger through the STORAGE form
# (`BindIntentLedger(cluster.storage).unretired()`), whatever form the
# schedulers keep it in: one form's records are the other's
LEDGER_FORMS = ["storage", "api"]


@pytest.mark.parametrize("ledger", LEDGER_FORMS + ["api-http"])
@pytest.mark.parametrize("site,binds_before_crash,intents_left", [
    ("pre_intent", 0, 0),   # decided, nothing durable yet
    ("post_intent", 0, 1),  # intent durable, no Binding committed
    ("post_bind", "all", 1),  # Bindings committed, intent unretired
])
def test_kill_matrix_restart_reconciles_exactly_once(
        site, binds_before_crash, intents_left, ledger):
    cluster = Cluster(ledger=ledger)
    try:
        s1 = cluster.boot()
        faultline.install(f"proc.crash@{site}:1")
        with pytest.raises(faultline.InjectedCrash):
            s1.schedule_pending()
        faultline.uninstall()

        # the crash left exactly the durable state the matrix row promises
        if binds_before_crash == "all":
            assert len(cluster.binder.bound) > 0
        else:
            assert len(cluster.binder.bound) == binds_before_crash
        led_view = BindIntentLedger(cluster.storage)
        assert len(led_view.unretired()) == intents_left

        # restart: a fresh incarnation reconciles, then drains the backlog
        s2 = cluster.boot()
        report = s2.recover(lookup=cluster.lookup)
        assert report.replayed_intents == intents_left
        if site == "post_bind":
            # informer truth showed every intent entry already bound — the
            # replay retired the record WITHOUT re-binding anything
            assert report.already_bound > 0 and report.completed == 0
        s2.run_until_idle()
        cluster.assert_exactly_once(s2)
    finally:
        cluster.close()


@pytest.mark.parametrize("ledger", LEDGER_FORMS)
def test_crash_during_takeover_second_successor_finishes(ledger):
    """The reconciler itself dies mid-replay (proc.crash@takeover): the
    intents it had not reached stay durable, and the NEXT successor's
    replay completes them — reconciliation is idempotent and restartable."""
    cluster = Cluster(ledger=ledger)
    try:
        s1 = cluster.boot()
        faultline.install("proc.crash@post_intent:1")
        with pytest.raises(faultline.InjectedCrash):
            s1.schedule_pending()
        faultline.uninstall()
        assert len(BindIntentLedger(cluster.storage).unretired()) == 1

        # first successor crashes INSIDE its reconciliation pass
        s2 = cluster.boot()
        faultline.install("proc.crash@takeover:1")
        with pytest.raises(faultline.InjectedCrash):
            s2.recover(lookup=cluster.lookup)
        faultline.uninstall()
        # the crashed takeover may have completed some binds but not
        # retired the intent — the record must still be there
        assert len(BindIntentLedger(cluster.storage).unretired()) == 1

        # second successor: replay sees whatever the first committed as
        # already_bound, completes the rest, retires the record
        s3 = cluster.boot()
        report = s3.recover(lookup=cluster.lookup)
        assert report.replayed_intents == 1
        s3.run_until_idle()
        cluster.assert_exactly_once(s3)
    finally:
        cluster.close()


# --------------------------------------------------------------------- #
# the apiserver-death matrix (ISSUE 19): the STORE dies mid-commit
# --------------------------------------------------------------------- #


@pytest.mark.durability
@pytest.mark.parametrize("site", [
    "wal:pre_fsync",    # record written, not yet durable (page cache)
    "wal:post_fsync",   # record durable, not yet applied to memory
    "wal:post_append",  # record durable AND applied, ack never returned
])
def test_apiserver_death_matrix_reboot_reconciles(site, tmp_path):
    """The apiserver dies inside the WAL commit of the wave's intent
    record. Process death (not machine death) leaves the appended bytes in
    the log at ALL three sites, so the rebooted store must surface the
    intent — committed-but-unacked writes may appear after reboot, and the
    successor's replay finishes the wave exactly-once."""
    cluster = Cluster(data_dir=str(tmp_path / "etcd"))
    try:
        s1 = cluster.boot()
        faultline.install(f"proc.crash@{site}:1")
        with pytest.raises(faultline.InjectedCrash):
            s1.schedule_pending()
        faultline.uninstall()
        assert len(cluster.binder.bound) == 0

        # reboot the apiserver from disk: the intent record survived the
        # kill regardless of whether its fsync or apply had happened
        cluster.reboot_storage()
        assert cluster.storage.kv.recovered
        assert len(BindIntentLedger(cluster.storage).unretired()) == 1

        s2 = cluster.boot()
        report = s2.recover(lookup=cluster.lookup)
        assert report.replayed_intents == 1
        s2.run_until_idle()
        cluster.assert_exactly_once(s2)
    finally:
        cluster.close()


@pytest.mark.durability
def test_double_kill_apiserver_then_takeover_crash(tmp_path):
    """The compound drill: the apiserver dies mid-commit, and then the
    FIRST successor scheduler dies mid-takeover while the rebooted store is
    barely back. A second store reboot replays the same WAL again
    (recovery is idempotent) and the third scheduler incarnation finishes
    to exactly-once."""
    cluster = Cluster(data_dir=str(tmp_path / "etcd"))
    try:
        s1 = cluster.boot()
        faultline.install("proc.crash@wal:post_append:1")
        with pytest.raises(faultline.InjectedCrash):
            s1.schedule_pending()
        faultline.uninstall()

        cluster.reboot_storage()
        assert len(BindIntentLedger(cluster.storage).unretired()) == 1

        # first successor crashes INSIDE its reconciliation pass
        s2 = cluster.boot()
        faultline.install("proc.crash@takeover:1")
        with pytest.raises(faultline.InjectedCrash):
            s2.recover(lookup=cluster.lookup)
        faultline.uninstall()

        # ... and the apiserver dies AGAIN before anyone retires the
        # intent: the second recovery replays the same log to the same
        # revisions (plus whatever the crashed takeover committed)
        rev_before = cluster.storage.kv.rev()
        cluster.reboot_storage()
        assert cluster.storage.kv.rev() == rev_before
        assert len(BindIntentLedger(cluster.storage).unretired()) == 1

        s3 = cluster.boot()
        report = s3.recover(lookup=cluster.lookup)
        assert report.replayed_intents == 1
        s3.run_until_idle()
        cluster.assert_exactly_once(s3)
    finally:
        cluster.close()


@pytest.mark.parametrize("ledger", LEDGER_FORMS)
def test_replay_releases_when_node_no_longer_fits(ledger):
    """An intent whose chosen node was meanwhile filled (or deleted) must
    RELEASE the pod back to the active queue — never force the stale
    placement — and the next wave places it elsewhere (the third node the
    crashed leader never considered)."""
    cluster = Cluster(n_nodes=3, n_pods=2, ledger=ledger)
    try:
        s1 = cluster.boot()
        faultline.install("proc.crash@post_intent:1")
        with pytest.raises(faultline.InjectedCrash):
            s1.schedule_pending()
        faultline.uninstall()
        intents = BindIntentLedger(cluster.storage).unretired()
        assert len(intents) == 1
        victim_nodes = set(intents[0].bindings.values())

        # the crashed leader's chosen nodes fill up before takeover
        s2 = cluster.boot()
        for i, nn in enumerate(sorted(victim_nodes)):
            filler = mkpod(f"filler-{i}", cpu="3950m", mem="7Gi")
            filler.node_name = nn
            cluster.pods[filler.key] = filler
            s2.on_pod_add(filler)
            cluster.binder.bound[filler.key] = nn
            cluster.binder.bind_log.append((filler.key, nn))
        report = s2.recover(lookup=cluster.lookup)
        assert report.released == 2 and report.completed == 0
        # released pods sit in exactly one lane: activeQ
        for key in intents[0].bindings:
            assert s2.queue.lanes(key) == (True, False, False)
        s2.run_until_idle()
        cluster.assert_exactly_once(s2)
    finally:
        cluster.close()


@pytest.mark.parametrize("ledger", LEDGER_FORMS)
def test_replay_drops_deleted_pods_and_skips_newer_tokens(ledger):
    cluster = Cluster(n_nodes=2, n_pods=2, ledger=ledger)
    try:
        s1 = cluster.boot()
        faultline.install("proc.crash@post_intent:1")
        with pytest.raises(faultline.InjectedCrash):
            s1.schedule_pending()
        faultline.uninstall()

        # both pods are deleted while the scheduler is down
        deleted = dict(cluster.pods)
        cluster.pods.clear()
        s2 = cluster.boot()
        # plant an intent from a NEWER leader (higher fencing token): a
        # stale reconciler must not touch it
        newer = cluster.make_ledger()
        newer.write_intent(cycle=99, token=10**6,
                           bindings={"default/future": "n0"})
        report = s2.recover(lookup=cluster.lookup)
        assert report.dropped == 2
        assert report.stale_skipped == 1
        left = BindIntentLedger(cluster.storage).unretired()
        assert len(left) == 1 and left[0].token == 10**6
        cluster.pods.update(deleted)  # restore for close bookkeeping
    finally:
        cluster.close()


# --------------------------------------------------------------------- #
# queue crash-requeue dedupe (satellite)
# --------------------------------------------------------------------- #


def test_crash_requeue_lands_in_exactly_one_lane():
    """A pod re-admitted from an unretired intent while ALSO parked in
    backoff (its pre-crash failure verdict) must end up in exactly one
    lane — activeQ — with its attempt history preserved."""
    from kubernetes_tpu.sched.queue import PriorityQueue

    q = PriorityQueue()
    pod = mkpod("dup")
    # the pod failed twice pre-crash and sits in backoff (a move request
    # at the pop cycle routes the failure to backoffQ)
    q.add(pod, now=0.0)
    q.pop_batch(8, now=0.0)
    q.move_all_to_active(now=0.0)
    q.add_unschedulable(pod, attempts=2, now=0.0)
    assert q.lanes(pod.key) == (False, True, False)

    lane = q.requeue_recovered(pod, attempts=1, now=0.0)
    assert lane == "active"
    assert q.lanes(pod.key) == (True, False, False)
    # attempts merged: max(recovery's 1, backoff's 2) — one entry, 2 kept
    batch = q.pop_batch(8, now=0.0)
    assert [(p.key, a) for p, a in batch] == [("default/dup", 3)]
    # the stale backoff heap tuple never resurrects the pod
    q.pump(now=100.0)
    assert q.lanes(pod.key) == (False, False, False)

    # idempotent when already active (the informer already re-queued it)
    q.add(pod, now=100.0)
    q.requeue_recovered(pod, attempts=1, now=100.0)
    assert q.lanes(pod.key) == (True, False, False)
    assert len(q.pop_batch(8, now=100.0)) == 1

    # unschedulable lane promotes too
    q.add_unschedulable(pod, attempts=1, now=200.0)
    assert q.lanes(pod.key) == (False, False, True)
    q.requeue_recovered(pod, attempts=1, now=200.0)
    assert q.lanes(pod.key) == (True, False, False)


# --------------------------------------------------------------------- #
# fencing (leader election + apiserver)
# --------------------------------------------------------------------- #


def _mk_lease_client():
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client

    api = APIServer()
    return api, Client.local(api)


def _force_claim(client, name, holder="b"):
    """Stomp the Lease as a usurping holder, retrying the CAS until OUR
    write lands (the incumbent may renew between our read and write —
    that race is the incumbent's renew winning, not a test failure)."""
    from kubernetes_tpu.machinery import errors

    for _ in range(50):
        lease = client.leases.get(name, "kube-system")
        lease["spec"]["holderIdentity"] = holder
        lease["spec"]["renewTime"] = time.time() + 3600
        lease["spec"]["leaseDurationSeconds"] = 3600
        lease["spec"]["leaseTransitions"] = \
            int(lease["spec"].get("leaseTransitions", 0)) + 1
        try:
            client.leases.update(lease, "kube-system")
            return
        except errors.StatusError as e:
            if not errors.is_conflict(e):
                raise
    raise AssertionError("could not land the usurper's claim in 50 tries")


def test_stale_token_bind_rejected_by_apiserver():
    """The server-side fence: after a leadership transition bumps the
    Lease generation, a Binding stamped with the OLD token is rejected
    with 409; the new token's Binding lands."""
    from kubernetes_tpu.client import LeaderElectionConfig, LeaderElector
    from kubernetes_tpu.machinery import errors

    api, client = _mk_lease_client()
    try:
        cfg = dict(lock_name="kube-scheduler", lease_duration=1.0,
                   renew_deadline=0.8, retry_period=0.1)
        a = LeaderElector(client, LeaderElectionConfig(identity="a", **cfg))
        a.start()
        assert a.wait_for_leadership(5)
        token_a = a.fencing_token
        a.crash()  # dies holding the lease — no release, token stays stale

        b = LeaderElector(client, LeaderElectionConfig(identity="b", **cfg))
        b.start()
        assert b.wait_for_leadership(10)  # waits out a's lease_duration
        assert b.fencing_token > token_a

        for i in range(2):
            client.pods.create({
                "apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": f"f-{i}", "namespace": "default"},
                "spec": {"containers": [{"name": "c", "image": "i"}]}})
        client.nodes.create({"apiVersion": "v1", "kind": "Node",
                             "metadata": {"name": "n0"}})

        # the deposed leader's in-flight bind: REJECTED, pod untouched
        stale_ann = {FENCING_TOKEN_ANNOTATION: str(token_a),
                     FENCING_LEASE_ANNOTATION: DEFAULT_FENCING_LEASE}
        with pytest.raises(errors.StatusError) as ei:
            client.pods.bind("f-0", "n0", "default", annotations=stale_ann)
        assert ei.value.code == 409 and "fencing token" in str(ei.value)
        assert not client.pods.get("f-0").get("spec", {}).get("nodeName")

        # the live leader's bind lands
        live_ann = {FENCING_TOKEN_ANNOTATION: str(b.fencing_token),
                    FENCING_LEASE_ANNOTATION: DEFAULT_FENCING_LEASE}
        client.pods.bind("f-1", "n0", "default", annotations=live_ann)
        assert client.pods.get("f-1")["spec"]["nodeName"] == "n0"

        # unstamped Bindings (non-HA callers) still pass
        client.pods.bind("f-0", "n0", "default")
        b.stop()
    finally:
        api.close()


def test_renew_cas_conflict_deposes_immediately():
    """Satellite regression: a CAS conflict during renew IS leadership
    loss — the holder must drop out within ~one retry period, never ride
    the retry-until-deadline window with two fencing tokens live. The
    conflict is injected deterministically (a one-shot conflicting proxy
    over the leases client — the moment a concurrent writer won the CAS
    race), so the exact branch is exercised, not the observed-live-holder
    sibling."""
    import threading

    from kubernetes_tpu.client import LeaderElectionConfig, LeaderElector
    from kubernetes_tpu.machinery import errors

    class ConflictOnce:
        """leases proxy whose next update is a lost CAS race."""

        def __init__(self, inner):
            self._inner = inner
            self.armed = False
            self.fired = False

        def get(self, *a, **k):
            return self._inner.get(*a, **k)

        def create(self, *a, **k):
            return self._inner.create(*a, **k)

        def update(self, *a, **k):
            if self.armed and not self.fired:
                self.fired = True
                raise errors.new_conflict(
                    "leases", "depose-drill",
                    "the object has been modified (simulated concurrent "
                    "writer winning the CAS race)")
            return self._inner.update(*a, **k)

    api, client = _mk_lease_client()
    try:
        proxy = ConflictOnce(client.leases)
        client.leases = proxy  # instance attr shadows __getattr__
        stopped = threading.Event()
        a = LeaderElector(client, LeaderElectionConfig(
            identity="a", lock_name="depose-drill", lease_duration=60.0,
            renew_deadline=30.0, retry_period=0.05,
            on_stopped_leading=stopped.set))
        a.start()
        assert a.wait_for_leadership(5)
        proxy.armed = True
        # deposition must land within ~retry periods, NOT the 30 s renew
        # deadline: on_stopped_leading fires the moment the conflict is
        # treated as loss (re-acquisition afterwards is fine and expected
        # here — the lease still carries a's identity)
        # generous against background-load scheduling hiccups; the bound
        # under proof is "well before the 30 s renew deadline"
        assert stopped.wait(10.0), (
            "holder kept leading after a renew CAS conflict — the "
            "two-fencing-tokens window is open")
        assert proxy.fired
        a.stop()
    finally:
        api.close()


def test_observed_live_usurper_deposes_immediately():
    """The sibling loss proof: the lease record names ANOTHER live holder
    (our renew lost the race entirely) — same immediate deposition. The
    deadline is generous against background compile threads from earlier
    tests; the REAL bound under proof is the 30 s renew_deadline the old
    code would have ridden out."""
    import threading

    from kubernetes_tpu.client import LeaderElectionConfig, LeaderElector

    api, client = _mk_lease_client()
    try:
        stopped = threading.Event()
        a = LeaderElector(client, LeaderElectionConfig(
            identity="a", lock_name="usurp-drill", lease_duration=60.0,
            renew_deadline=30.0, retry_period=0.05,
            on_stopped_leading=stopped.set))
        a.start()
        assert a.wait_for_leadership(5)
        _force_claim(client, "usurp-drill")
        # after ONE failed renew pass the usurper is observed as live: a
        # must drop leadership promptly, never at the 30 s renew deadline
        assert stopped.wait(10.0), (
            "holder kept leading after observing a live usurper")
        assert not a.is_leader  # the usurper's live lease blocks re-acquire
        a.stop()
    finally:
        api.close()


# --------------------------------------------------------------------- #
# the end-to-end kill → warm-standby takeover drill
# --------------------------------------------------------------------- #


def test_kill_takeover_drill_end_to_end():
    """Two full SchedulerServers over one apiserver: A leads and starts
    binding, a chaos kill takes A down mid-cycle (after Bindings, before
    the intent retires — the nastiest row of the matrix), B's warm standby
    takes over: reconciles the orphaned intent, drains the backlog, and
    the cluster ends with every pod bound exactly once. The consistency
    sweep (sched/debugger.py) runs once on the survivor and finds nothing
    to heal."""
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.sched.debugger import ConsistencySweeper
    from kubernetes_tpu.sched.server import SchedulerServer

    n_pods = 24
    api = APIServer()
    client_a = Client.local(api)
    client_b = Client.local(api)
    lease_cfg = dict(lease_duration=1.5, renew_deadline=1.0,
                     retry_period=0.1)
    caps = {"capacity": {"cpu": "16", "memory": "64Gi", "pods": "110"},
            "allocatable": {"cpu": "16", "memory": "64Gi", "pods": "110"}}
    a = b = None
    try:
        for i in range(4):
            client_a.nodes.create({"apiVersion": "v1", "kind": "Node",
                                   "metadata": {"name": f"n{i}"},
                                   "status": caps})
        a = SchedulerServer(
            client_a, leader_elect=True, cycle_interval=0.02,
            ledger=BindIntentLedger(api.storage, identity="a"),
            lease_config=dict(identity="a", **lease_cfg),
            standby_warm_interval=0.2).start()
        assert a.elector.wait_for_leadership(10)

        # B boots as the warm standby: informers live, never binds
        b = SchedulerServer(
            client_b, leader_elect=True, cycle_interval=0.02,
            ledger=BindIntentLedger(api.storage, identity="b"),
            lease_config=dict(identity="b", **lease_cfg),
            standby_warm_interval=0.2).start()

        for i in range(n_pods):
            client_a.pods.create({
                "apiVersion": "v1", "kind": "Pod",
                "metadata": {"name": f"d-{i}", "namespace": "default"},
                "spec": {"containers": [{
                    "name": "c", "image": "i",
                    "resources": {"requests": {"cpu": "100m",
                                               "memory": "64Mi"}}}]}})

        def bound_count():
            return sum(1 for p in client_b.pods.list("default")["items"]
                       if p.get("spec", {}).get("nodeName"))

        # let A bind at least one pod, then kill it at the worst moment:
        # Bindings committed, intent NOT retired
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and bound_count() == 0:
            time.sleep(0.05)
        assert bound_count() > 0, "leader never started binding"
        faultline.install("proc.crash@post_bind:1")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and \
                faultline.active().fired("proc.crash") == 0:
            time.sleep(0.05)
        crashed = faultline.active().fired("proc.crash") > 0
        faultline.uninstall()
        t_kill = time.monotonic()
        a.crash()  # the process is gone: lease unreleased, loop dead

        if crashed:
            # the kill landed between bind and retire: the orphaned
            # intent is on record for B to reconcile
            assert len(a.scheduler.ledger.unretired()) >= 1

        # warm-standby takeover: B must acquire (waiting out A's lease),
        # reconcile, and finish the job
        assert b.elector.wait_for_leadership(30), "standby never took over"
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and bound_count() < n_pods:
            time.sleep(0.1)
        takeover_s = time.monotonic() - t_kill
        assert bound_count() == n_pods, (
            f"lost pods: {n_pods - bound_count()} after takeover")

        # exactly-once: every pod has ONE node, no intent left, and B ran
        # a reconciliation pass (B's loop thread runs it on its first led
        # beat — poll rather than race it)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and b.takeovers == 0:
            time.sleep(0.05)
        assert b.takeovers >= 1, (
            f"recovery never ran: {b.last_recovery_error!r}")
        assert b.last_recovery is not None or not crashed
        # B's loop thread keeps draining the backlog concurrently: its OWN
        # in-flight wave legitimately holds an intent between write and
        # retire, so "no intent left" is an EVENTUAL property — poll it
        # (under full-suite load the commit window is wide enough to race
        # a point-in-time read)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and \
                b.scheduler.ledger.unretired():
            time.sleep(0.05)
        assert b.scheduler.ledger.unretired() == []
        assert takeover_s < 60.0

        # consistency sweep on the survivor: truth and cache agree; the
        # sweep itself is exercised (counted) even with zero divergence
        sweeper = ConsistencySweeper(b.scheduler, client_b)
        found = sweeper.sweep()
        assert sweeper.sweeps == 1
        assert all(v == 0 for v in found.values()), found
    finally:
        if a is not None and not a._crashed:
            a.stop()
        elif a is not None:
            a.crash()
        if b is not None:
            b.stop()
        api.close()


def test_consistency_sweep_heals_injected_divergence():
    """Satellite: the sweep detects a cache/informer divergence (a node
    the informer delivered but the cache lost, a phantom pod), heals from
    apiserver truth, and forces the next snapshot onto the full re-encode
    path."""
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.sched.debugger import ConsistencySweeper
    from kubernetes_tpu.sched.scheduler import RecordingBinder

    api = APIServer()
    client = Client.local(api)
    try:
        s = Scheduler(binder=RecordingBinder(),
                      base_dims=Dims(N=16, P=16, E=64))
        caps = {"capacity": {"cpu": "4", "memory": "8Gi", "pods": "110"},
                "allocatable": {"cpu": "4", "memory": "8Gi", "pods": "110"}}
        for i in range(3):
            client.nodes.create({"apiVersion": "v1", "kind": "Node",
                                 "metadata": {"name": f"n{i}"},
                                 "status": caps})
            s.on_node_add(mknode(f"n{i}"))
        s.cache.snapshot(s.encoder, [], s.base_dims)

        # divergence 1: the cache silently lost a node
        s.cache.remove_node("n2")
        # divergence 2: the cache holds a pod the apiserver never saw
        phantom = mkpod("phantom")
        phantom.node_name = "n0"
        s.cache.add_pod(phantom)

        sweeper = ConsistencySweeper(s, client, log=lambda *_: None)
        found = sweeper.sweep()
        assert found["nodes_missing"] == 1
        assert found["pods_stale"] == 1
        assert sweeper.heals == 1
        # healed: truth restored, next snapshot is a FULL re-encode
        assert {n.name for n in s.cache.nodes()} == {"n0", "n1", "n2"}
        assert s.cache.get_pod("default/phantom") is None
        s.cache.snapshot(s.encoder, [], s.base_dims)
        assert s.cache.last_snapshot_mode == "full"
        # clean second sweep: nothing found, no second heal
        found2 = sweeper.sweep()
        assert all(v == 0 for v in found2.values())
        assert sweeper.heals == 1
    finally:
        api.close()


def test_warm_standby_compiles_without_touching_state():
    """warm_standby keeps the executable + snapshot hot but never pops,
    assumes, or binds — the read-only contract that makes it safe to run
    while NOT leading."""
    from kubernetes_tpu.sched.scheduler import RecordingBinder

    binder = RecordingBinder()
    s = Scheduler(binder=binder, base_dims=Dims(N=16, P=16, E=64))
    s.prewarmer.min_axis = 1  # allow the tiny test shape to warm
    for i in range(4):
        s.on_node_add(mknode(f"n{i}"))
    for i in range(8):
        s.on_pod_add(mkpod(f"p{i}"))
    before = s.queue.lengths()
    s.warm_standby()
    s.prewarmer.wait(timeout=120)
    assert s.queue.lengths() == before          # nothing popped
    assert binder.bound == []                   # nothing bound
    assert s.cache.counts()[2] == 0             # nothing assumed
    assert len(s.prewarmer.compiled) >= 1       # the signature IS warm
    # the first led wave hits the prewarmed executable + patched snapshot
    stats = s.schedule_pending()
    assert stats.scheduled == 8
