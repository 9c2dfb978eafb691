"""Incremental snapshot correctness: the patch path must (a) do O(changed)
host work — no full re-encode, no full re-upload — and (b) be semantically
indistinguishable from a from-scratch full encode of the same cluster state.

The reference's contract is UpdateNodeInfoSnapshot's generation diffing
(/root/reference/pkg/scheduler/internal/cache/cache.go:204-255): only nodes
whose generation moved are copied into the snapshot. Here the analog is dirty
node/pod row tracking in SchedulerCache plus a device-side row scatter
(state/cache.py:_patch_snapshot); these tests are what keeps the claim in
state/encode.py's docstring true.
"""

import random

import jax
import numpy as np
import pytest

from kubernetes_tpu.api.types import (
    Affinity, LabelSelector, Node, Pod, PodAffinityTerm, Resources,
    TopologySpreadConstraint, UnsatisfiableAction,
)
from kubernetes_tpu.sched.cycle import _schedule_batch, snapshot_with_keys
from kubernetes_tpu.state.cache import SchedulerCache
from kubernetes_tpu.state.encode import Encoder

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"


def mknode(name, zone="z0", cpu="4", mem="16Gi"):
    return Node(name=name,
                labels={ZONE: zone, HOSTNAME: name},
                allocatable=Resources.make(cpu=cpu, memory=mem, pods=110))


def mkpod(name, app="a", cpu="500m", mem="1Gi", node=None, anti=False,
          spread=False, creation=0):
    sel = LabelSelector.of(match_labels={"app": app})
    affinity = Affinity(anti_required=(
        PodAffinityTerm(selector=sel, topology_key=HOSTNAME),)) if anti \
        else Affinity()
    tsc = (TopologySpreadConstraint(
        max_skew=1, topology_key=ZONE,
        when_unsatisfiable=UnsatisfiableAction.DO_NOT_SCHEDULE,
        selector=sel),) if spread else ()
    return Pod(name=name, labels={"app": app},
               requests=Resources.make(cpu=cpu, memory=mem),
               affinity=affinity, topology_spread=tsc,
               node_name=node or "", creation_index=creation)


def build_cache(n_nodes=12, n_bound=8):
    cache = SchedulerCache()
    enc = Encoder()
    for i in range(n_nodes):
        cache.add_node(mknode(f"n{i}", zone=f"z{i % 3}"))
    for i in range(n_bound):
        cache.add_pod(mkpod(f"b{i}", app=f"g{i % 2}", node=f"n{i % n_nodes}",
                            anti=(i % 2 == 0), creation=i))
    return cache, enc


def schedule_names(cache, enc, pending):
    snap, keys = snapshot_with_keys(cache, enc, pending, None)
    res = _schedule_batch(snap.tables, snap.pending, keys, snap.dims.D,
                          snap.existing, has_node_name=snap.dims.has_node_name)
    idx = np.asarray(jax.device_get(res.node))
    return [snap.node_order[i] if i >= 0 else None
            for i in idx[: len(pending)]]


def oracle_names(cache, pending):
    """Same cluster state scheduled through a FRESH cache + encoder (cold full
    encode) — the from-scratch reference the patched snapshot must match.
    Nodes are inserted in the live snapshot's slot order so node-index
    tie-breaks (PARITY #1: deterministic argmax in place of the reference's
    random selectHost) agree between the two encodings."""
    order = [nm for nm in (cache._snapshot.node_order if cache._snapshot
                           else []) if nm]
    by_name = {n.name: n for n in cache.nodes()}
    fresh = SchedulerCache()
    for nm in order:
        if nm in by_name:
            fresh.add_node(by_name.pop(nm))
    for n in by_name.values():
        fresh.add_node(n)
    for p in cache.scheduled_pods():
        fresh.add_pod(p)
    return schedule_names(fresh, Encoder(), pending)


def test_second_snapshot_is_cached():
    cache, enc = build_cache()
    pending = [mkpod("p0", app="g0", creation=100)]
    snapshot_with_keys(cache, enc, pending, None)
    assert cache.last_snapshot_mode == "full"
    snapshot_with_keys(cache, enc, pending, None)
    assert cache.last_snapshot_mode == "cached"


def test_node_churn_takes_patch_path_with_o_changed_rows(monkeypatch):
    cache, enc = build_cache(n_nodes=12, n_bound=8)
    pending = [mkpod("p0", app="g0", creation=100)]
    s1, _ = snapshot_with_keys(cache, enc, pending, None)
    assert cache.last_snapshot_mode == "full"

    calls = []
    orig = Encoder.encode_node_row

    def counting(self, arrays, i, n, pods, d):
        calls.append(n.name)
        return orig(self, arrays, i, n, pods, d)

    monkeypatch.setattr(Encoder, "encode_node_row", counting)
    cache.update_node(mknode("n3", zone="z1", cpu="8"))
    s2, _ = snapshot_with_keys(cache, enc, pending, None)
    assert cache.last_snapshot_mode == "patch"
    assert calls == ["n3"], "only the dirty node row may be re-encoded"
    assert cache.last_patch_rows == 1
    # untouched device tables are REUSED, not re-uploaded
    assert s2.tables.reqs.vec is s1.tables.reqs.vec
    assert s2.tables.classes.rid is s1.tables.classes.rid
    assert s2.existing.cls is s1.existing.cls
    assert s2.pending.cls is s1.pending.cls


def test_patched_snapshot_matches_fresh_full_encode():
    cache, enc = build_cache(n_nodes=12, n_bound=8)
    pending = [mkpod(f"p{i}", app=f"g{i % 2}", anti=(i % 3 == 0),
                     spread=(i % 2 == 0), creation=100 + i) for i in range(6)]
    schedule_names(cache, enc, pending)  # builds the full snapshot

    # churn: node update, pod assume, pod remove, node add
    cache.update_node(mknode("n1", zone="z2", cpu="2"))
    cache.assume_pod(mkpod("x0", app="g1", creation=50), "n2")
    cache.remove_pod("default/b3")
    cache.add_node(mknode("n12", zone="z0"))

    got = schedule_names(cache, enc, pending)
    assert cache.last_snapshot_mode == "patch"
    assert got == oracle_names(cache, pending)
    assert any(g is not None for g in got)


def test_node_remove_reroutes_pods_and_matches_oracle():
    cache, enc = build_cache(n_nodes=6, n_bound=6)
    pending = [mkpod("p0", app="g0", anti=True, creation=100),
               mkpod("p1", app="g1", creation=101)]
    schedule_names(cache, enc, pending)
    cache.remove_node("n2")  # b2 still bound there; its row must detach
    got = schedule_names(cache, enc, pending)
    assert cache.last_snapshot_mode == "patch"
    assert got == oracle_names(cache, pending)
    assert "n2" not in [g for g in got if g]


def test_pod_bound_before_node_exists_reattaches_on_node_add():
    """Watch-ordering race: a bound pod arrives before its node. When the node
    later gains a slot on the patch path, the pod's row must re-point at it so
    affinity counts and usage see it (code-review regression)."""
    cache, enc = build_cache(n_nodes=4, n_bound=2)
    pending = [mkpod("p0", app="late", anti=True, creation=100)]
    schedule_names(cache, enc, pending)
    # pod lands on a node the cache has not seen yet
    cache.add_pod(mkpod("orphan", app="late", node="nlate", anti=True,
                        creation=10))
    schedule_names(cache, enc, pending)
    # node arrives; its slot allocation must re-row the orphan pod
    cache.add_node(mknode("nlate", zone="z1"))
    got = schedule_names(cache, enc, pending)
    assert cache.last_snapshot_mode == "patch"
    assert got == oracle_names(cache, pending)
    # the orphan's anti-affinity now blocks p0 from nlate
    assert got[0] != "nlate"


def test_new_topology_key_stays_on_patch_path(monkeypatch):
    """A never-seen topologyKey used to flip the 0.1s patch into the ~full
    re-encode fallback (round-3 verdict weakness 4). As long as the key fits
    the existing K/D capacities, only the new [N] topo/domain columns are
    derived and shipped — zero node rows re-encoded — and the constraint is
    ENFORCED: the scenario is built so dropping it changes the placement
    (unconstrained scoring prefers the skew-violating rack)."""
    cache = SchedulerCache()
    enc = Encoder()
    for i in range(4):
        rack = "rA" if i < 2 else "rB"
        cache.add_node(Node(
            name=f"n{i}",
            labels={ZONE: "z0", HOSTNAME: f"n{i}",
                    "example.com/rack": rack},
            allocatable=Resources.make(cpu="4", memory="16Gi", pods=110)))
    # rack rA holds the matching pods (tiny requests); rack rB is loaded
    # with big NON-matching pods, so unconstrained least-allocated scoring
    # prefers rA — only the spread constraint forces rB
    cache.add_pod(mkpod("g1a", app="g1", cpu="100m", node="n0",
                        anti=True, creation=0))
    cache.add_pod(mkpod("g1b", app="g1", cpu="100m", node="n1", creation=1))
    cache.add_pod(mkpod("biga", app="big", cpu="3", node="n2", creation=2))
    cache.add_pod(mkpod("bigb", app="big", cpu="3", node="n3", creation=3))
    warm = [mkpod("w0", app="g0", creation=90)]
    schedule_names(cache, enc, warm)  # full encode: interns hostname

    calls = []
    orig = Encoder.encode_node_row

    def counting(self, arrays, i, n, pods, d):
        calls.append(n.name)
        return orig(self, arrays, i, n, pods, d)

    monkeypatch.setattr(Encoder, "encode_node_row", counting)
    sel = LabelSelector.of(match_labels={"app": "g1"})
    rack_spread = Pod(
        name="p-rack", labels={"app": "g1"},
        requests=Resources.make(cpu="100m", memory="256Mi"),
        topology_spread=(TopologySpreadConstraint(
            max_skew=1, topology_key="example.com/rack",
            when_unsatisfiable=UnsatisfiableAction.DO_NOT_SCHEDULE,
            selector=sel),),
        creation_index=100)
    pending = [rack_spread]
    got = schedule_names(cache, enc, pending)
    assert cache.last_snapshot_mode == "patch", \
        "a new topologyKey within capacity must not force a full re-encode"
    assert calls == [], "no node row may be re-encoded for a new topo key"
    # rA has 2 matching pods, rB has 0: placing in rA gives skew 3 > 1, so
    # the patched lattice must send the pod to rB despite rB's load
    assert got[0] in ("n2", "n3"), \
        "hard topology-spread on the new key must be enforced"
    assert got == oracle_names(cache, pending)


def test_patch_path_registers_domains_of_dirty_nodes_only(monkeypatch):
    """A wave's snapshot asks the encoder for the topology domains of the
    nodes that can have changed, not of the fleet: on the patch path the
    dirty nodes, and every node once a topology key is new (each node owns
    a cell of the new [N, K] column, and its backfill must agree with a
    from-scratch encode of the same state)."""
    def racked(i, cpu="4"):
        return Node(
            name=f"n{i}",
            labels={ZONE: f"z{i % 2}", HOSTNAME: f"n{i}",
                    "example.com/rack": "rA" if i < 3 else "rB"},
            allocatable=Resources.make(cpu=cpu, memory="16Gi", pods=110))

    cache = SchedulerCache()
    enc = Encoder()
    for i in range(6):
        cache.add_node(racked(i))
    cache.add_pod(mkpod("g1a", app="g1", cpu="100m", node="n0", creation=0))
    cache.add_pod(mkpod("g1b", app="g1", cpu="100m", node="n1", creation=1))
    cache.add_pod(mkpod("biga", app="big", cpu="3", node="n3", creation=2))
    cache.add_pod(mkpod("bigb", app="big", cpu="3", node="n4", creation=3))
    cache.add_pod(mkpod("bigc", app="big", cpu="3", node="n5", creation=4))
    schedule_names(cache, enc, [mkpod("w0", app="g0", spread=True,
                                      creation=90)])
    assert cache.last_snapshot_mode == "full"

    asked = []
    orig = Encoder.register_node_domains

    def counting(self, n):
        asked.append(n.name)
        return orig(self, n)

    monkeypatch.setattr(Encoder, "register_node_domains", counting)
    # a pod lands on n2, n4 changes: two dirty nodes of six
    cache.add_pod(mkpod("late", app="g0", node="n2", creation=5))
    cache.update_node(racked(4, cpu="8"))
    pending = [mkpod("p0", app="g0", spread=True, creation=100)]
    got = schedule_names(cache, enc, pending)
    assert cache.last_snapshot_mode == "patch"
    assert set(asked) == {"n2", "n4"}, \
        "a patch snapshot may ask only for its dirty nodes' domains"
    assert got == oracle_names(cache, pending)
    # nothing dirty: nothing asked
    del asked[:]
    schedule_names(cache, enc, [mkpod("p1", app="g0", spread=True,
                                      creation=101)])
    assert cache.last_snapshot_mode == "patch" and asked == []

    # a topology key no pod named before: every node's domain under it
    sel = LabelSelector.of(match_labels={"app": "g1"})
    rack_spread = [Pod(
        name="p-rack", labels={"app": "g1"},
        requests=Resources.make(cpu="100m", memory="256Mi"),
        topology_spread=(TopologySpreadConstraint(
            max_skew=1, topology_key="example.com/rack",
            when_unsatisfiable=UnsatisfiableAction.DO_NOT_SCHEDULE,
            selector=sel),),
        creation_index=102)]
    got = schedule_names(cache, enc, rack_spread)
    assert cache.last_snapshot_mode == "patch"
    assert set(asked) == {f"n{i}" for i in range(6)}
    # rA holds the two matching pods, rB none: only rB keeps the skew
    assert got[0] in ("n3", "n4", "n5")
    assert got == oracle_names(cache, rack_spread)
    k = enc.vocabs.topo_keys.get("example.com/rack")
    racks = cache._staging_nodes.domain[:6, k]
    assert len(set(racks[:3])) == 1 and len(set(racks[3:])) == 1 \
        and racks[0] != racks[3] and (racks >= 0).all()
    # and the key count is the snapshot's again: back to the dirty nodes
    del asked[:]
    cache.update_node(racked(1, cpu="6"))
    schedule_names(cache, enc, [mkpod("p2", app="g0", creation=103)])
    assert cache.last_snapshot_mode == "patch" and set(asked) == {"n1"}


def test_capacity_growth_falls_back_to_full():
    cache, enc = build_cache(n_nodes=12, n_bound=4)
    pending = [mkpod("p0", app="g0", creation=100)]
    snapshot_with_keys(cache, enc, pending, None)
    for i in range(30):  # exceed the bucketed node capacity (16)
        cache.add_node(mknode(f"grow{i}"))
    snapshot_with_keys(cache, enc, pending, None)
    assert cache.last_snapshot_mode == "full"
    got = schedule_names(cache, enc, pending)
    assert got == oracle_names(cache, pending)


def test_node_churn_does_not_grow_domains_forever():
    """Hostname-keyed constraints make every node name a domain id. Node
    replacement churn must not ratchet the D capacity up forever: each full
    re-encode compacts the domain maps to the live node set."""
    cache, enc = build_cache(n_nodes=8, n_bound=4)  # anti pods → hostname key
    pending = [mkpod("p0", app="g0", anti=True, creation=100)]
    schedule_names(cache, enc, pending)
    for gen in range(6):  # 6 generations of full node replacement
        for n in list(cache.nodes()):
            if n.name.startswith(("n", f"gen{gen - 1}-")):
                cache.remove_node(n.name)
        for i in range(8):
            cache.add_node(mknode(f"gen{gen}-{i}", zone=f"z{i % 3}"))
        schedule_names(cache, enc, pending)
    live_hostnames = len(cache.nodes())
    assert live_hostnames == 8
    # 48 distinct hostnames ever seen; D must track the ~8 live ones
    assert cache._snapshot.dims.D <= 16, cache._snapshot.dims.D
    assert schedule_names(cache, enc, pending) == oracle_names(cache, pending)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_randomized_churn_replay_matches_oracle(seed):
    """Property: after ANY sequence of cache mutations, scheduling through the
    patched snapshot equals scheduling the same state from scratch."""
    rng = random.Random(seed)
    cache, enc = build_cache(n_nodes=10, n_bound=6)
    pending = [mkpod(f"p{i}", app=f"g{i % 3}", anti=(i % 2 == 0),
                     spread=(i % 3 == 0), creation=100 + i) for i in range(5)]
    schedule_names(cache, enc, pending)

    next_id = [100]
    for step in range(12):
        op = rng.choice(["node_up", "assume", "forget_or_remove", "node_add"])
        if op == "node_up":
            name = rng.choice([n.name for n in cache.nodes()])
            cache.update_node(mknode(name, zone=f"z{rng.randrange(4)}",
                                     cpu=rng.choice(["2", "4", "8"])))
        elif op == "assume":
            k = next_id[0]
            next_id[0] += 1
            nodes = [n.name for n in cache.nodes()]
            cache.assume_pod(
                mkpod(f"c{k}", app=f"g{k % 3}", creation=k), rng.choice(nodes))
        elif op == "forget_or_remove":
            pods = cache.scheduled_pods()
            if pods:
                victim = rng.choice(pods)
                if cache.is_assumed(victim.key):
                    cache.forget_pod(victim.key)
                else:
                    cache.remove_pod(victim.key)
        else:
            k = next_id[0]
            next_id[0] += 1
            cache.add_node(mknode(f"a{k}", zone=f"z{k % 4}"))
        got = schedule_names(cache, enc, pending)
        assert got == oracle_names(cache, pending), f"divergence at step {step}"


class TestLabelProjection:
    """Class identity projects pod labels onto selector-REFERENCED keys only
    (encode.py class_id): unreferenced labels cannot change any engine
    decision, so label-diverse-but-spec-identical pods share one class —
    the class-collapse that makes BASELINE config 5 tractable — while a key
    becoming referenced later forces a projection re-walk."""

    def test_unreferenced_labels_collapse_classes(self):
        enc = Encoder()
        pods = [Pod(name=f"p{i}", labels={"app": f"job-{i}"},
                    requests=Resources.make(cpu="1", memory="1Gi"),
                    creation_index=i) for i in range(100)]
        for p in pods:
            enc.pod_row(p)
        assert len(enc.class_reg) == 1
        assert not enc.classes_stale

    def test_late_referenced_key_splits_and_still_matches(self):
        """An affinity pod arriving AFTER label-diverse pods were interned
        must still match them correctly: the cache re-walks under the
        widened projection (full snapshot), and placement respects the
        affinity."""
        cache = SchedulerCache()
        enc = Encoder()
        for z, name in (("z0", "n0"), ("z1", "n1")):
            cache.add_node(mknode(name, zone=z))
        # two label-diverse bound pods, no selectors anywhere yet
        for i, (node, app) in enumerate((("n0", "red"), ("n1", "blue"))):
            cache.add_pod(Pod(name=f"b{i}", labels={"color": app},
                              requests=Resources.make(cpu="100m",
                                                      memory="128Mi"),
                              node_name=node, creation_index=i))
        snap1, keys1 = snapshot_with_keys(cache, enc, [], None)
        assert cache.last_snapshot_mode == "full"
        # both bound pods share one class: "color" is unreferenced
        assert len({int(x) for x in np.asarray(
            jax.device_get(snap1.existing.cls))[:2]}) == 1

        # now a pending pod REQUIRES zone affinity to color=red
        want_red = Pod(
            name="seeker", labels={},
            requests=Resources.make(cpu="100m", memory="128Mi"),
            affinity=Affinity(pod_required=(PodAffinityTerm(
                selector=LabelSelector.of(match_labels={"color": "red"}),
                topology_key=ZONE),)),
            creation_index=10)
        snap2, keys2 = snapshot_with_keys(cache, enc, [want_red], None)
        # the projection widened: full re-walk, classes split
        assert cache.last_snapshot_mode == "full"
        assert len({int(x) for x in np.asarray(
            jax.device_get(snap2.existing.cls))[:2]}) == 2
        res = _schedule_batch(snap2.tables, snap2.pending, keys2,
                              snap2.dims.D, snap2.existing)
        node_idx = int(np.asarray(jax.device_get(res.node))[0])
        assert snap2.node_order[node_idx] == "n0"  # the red pod's zone
