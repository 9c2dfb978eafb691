"""The seeded generators give every seed the same work, and the reference
sees what it is there to see."""

import collections
import json
import os

import pytest

from benchmarks.harness import objects, reference

HERE = os.path.dirname(os.path.abspath(__file__))
SEEDS = [0, 1, 2, 3, 7, 11, 101, 4242, 99991, 2 ** 31 - 1, 2 ** 31 + 12345,
         3_000_000_019]


def config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def shape_counts(cfg, seed, count):
    groups = objects.Groups(cfg, seed, count // cfg["groups"])
    pods = objects.pending_pods(groups, count, seed, "t")
    by_role = collections.Counter(groups.role[g] for g in range(groups.n))
    by_role_tier = collections.Counter(
        (groups.role[g], tuple(groups.tier[g])) for g in range(groups.n))
    by_prio = collections.Counter(p["spec"]["priority"] for p in pods)
    by_tier = collections.Counter(
        p["spec"]["containers"][0]["resources"]["requests"]["cpu"]
        for p in pods)
    partners = sorted(groups.role[p] for p in groups.partner.values())
    return by_role, by_role_tier, by_prio, by_tier, partners


@pytest.mark.parametrize("name", ["flagship-5k", "density-1k"])
def test_every_seed_is_the_same_work(name):
    cfg = config(name)
    first = shape_counts(cfg, SEEDS[0], 800)
    names = set()
    for seed in SEEDS[1:]:
        assert shape_counts(cfg, seed, 800) == first
    for seed in SEEDS:
        groups = objects.Groups(cfg, seed, 16)
        pods = objects.pending_pods(groups, 800, seed, "t")
        names.add(tuple(p["metadata"]["name"] for p in pods))
    assert len(names) == len(SEEDS)   # but never the same objects


def test_flagship_roles_are_the_published_ones():
    cfg = config("flagship-5k")
    groups = objects.Groups(cfg, 5, 1000)
    roles = collections.Counter(groups.role.values())
    assert roles == {"spread": 17, "anti": 17, "affinity": 16}
    assert groups.max_skew == 125
    for g, partner in groups.partner.items():
        assert groups.role[g] == "affinity" and groups.role[partner] == "anti"
    assert len(set(groups.partner.values())) == 16
    assert {groups.priority(g) for g in range(50)} == {0, 1, 2}


@pytest.mark.parametrize("nodes,count", [(5000, 50000), (64, 1600)])
def test_prebound_population_keeps_its_own_constraints(nodes, count):
    cfg = {**config("flagship-5k"), "nodes": nodes}
    for seed in SEEDS[:3]:
        groups = objects.Groups(cfg, seed, count // 50)
        pods = objects.prebound_pods(groups, nodes, count)
        assert len(pods) == count
        assert reference.final_state(objects.make_nodes(cfg), pods,
                                     check_spread=True) == []


def _small():
    cfg = {**config("flagship-5k"), "nodes": 32}
    groups = objects.Groups(cfg, 1, 16)
    return cfg, groups, objects.make_nodes(cfg)


def test_reference_sees_each_violation():
    cfg, groups, nodes = _small()
    anti = next(g for g in range(50) if groups.role[g] == "anti")
    aff = next(g for g in range(50) if groups.role[g] == "affinity")
    # two replicas of an anti-affinity group on one node
    pods = [groups.pod(anti, "a", "node-0"), groups.pod(anti, "b", "node-0")]
    assert any("anti-affinity" in v for v in
               reference.final_state(nodes, pods, True))
    # an affinity pod with no partner in its zone
    pods = [groups.pod(aff, "c", "node-0"),
            groups.pod(groups.partner[aff], "d", "node-1")]
    assert any("affinity: c" in v for v in
               reference.final_state(nodes, pods, True))
    # a zone holding more than maxSkew above the emptiest
    spread = next(g for g in range(50) if groups.role[g] == "spread")
    pods = [groups.pod(spread, f"s{i}", f"node-{16 * (i % 2)}")
            for i in range(groups.max_skew + 1)]
    bad = reference.final_state(nodes, pods, True)
    assert any(v.startswith("spread") for v in bad)
    assert not any(v.startswith("spread") for v in
                   reference.final_state(nodes, pods, False))
    # a node over its pod count
    plain = [groups.pod(spread, f"p{i}", "node-3") for i in range(111)]
    assert any("pods 111 > allocatable 110" in v for v in
               reference.final_state(nodes, plain, False))


def test_replay_holds_each_binding_to_its_turn():
    cfg, groups, nodes = _small()
    aff = next(g for g in range(50) if groups.role[g] == "affinity")
    partner = groups.partner[aff]
    a, p = groups.pod(aff, "a"), groups.pod(partner, "p")
    by_name = {"a": a, "p": p}
    shapes = [a, p]
    # partner first, same zone: sound
    ok = [("bound", "p", "node-0"), ("bound", "a", "node-16")]
    assert reference.replay(nodes, [], ok, by_name, shapes) == (2, [])
    # the affinity pod before its partner exists: infeasible at its turn,
    # though the final state is the same
    early = [("bound", "a", "node-16"), ("bound", "p", "node-0")]
    checked, bad = reference.replay(nodes, [], early, by_name, shapes)
    assert checked == 2 and len(bad) == 1 and "required affinity" in bad[0]
    # a deletion frees the node for the next replica of an anti group
    p2 = groups.pod(partner, "p2")
    hist = [("bound", "p", "node-0"), ("deleted", "p", ""),
            ("bound", "p2", "node-0")]
    assert reference.replay(nodes, [], hist, {"p": p, "p2": p2},
                            shapes)[1] == []
    hist = [("bound", "p", "node-0"), ("bound", "p2", "node-0")]
    assert len(reference.replay(nodes, [], hist, {"p": p, "p2": p2},
                                shapes)[1]) == 1


def test_quantities():
    assert reference.milli_cpu("250m") == 250
    assert reference.milli_cpu("32") == 32000
    assert reference.kib("128Gi") == 134217728
    assert reference.kib("131072Ki") == 131072
