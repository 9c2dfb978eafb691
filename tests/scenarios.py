"""Named adversarial clusters `(nodes, existing, pending)`: replica bursts
that stress one mechanism of the engine each (capacity exhaustion, classes
that conflict with themselves on a node, priority tiers, weight that one
class's placements write and a later class's scores read).

They are inputs, by name, of `tests/test_waves.py`'s
`test_wave_replay_is_valid_greedy_execution` and
`test_waves_with_product_equal_waves_with_scatter`. `PLACED` holds, for the
scenarios where every greedy execution places the same number of pods
whatever its interleaving, that number.
"""

import dataclasses
import functools
import random

from kubernetes_tpu.api.types import (
    Affinity,
    HostPort,
    LabelSelector,
    Node,
    Pod,
    PodAffinityTerm,
    Resources,
    VolumeRef,
    WeightedPodAffinityTerm,
)

from test_golden import rand_node, rand_pod

HOSTNAME = "kubernetes.io/hostname"


def _nodes(n, cpu, memory, pods, hostname=False):
    return [Node(name=f"n{i}", labels={HOSTNAME: f"n{i}"} if hostname else {},
                 allocatable=Resources.make(cpu=cpu, memory=memory, pods=pods))
            for i in range(n)]


def replica(template, i):
    return dataclasses.replace(template, name=f"p{i}", creation_index=i)


def homogeneous_spread():
    """One deployment's replicas spreading over uniform nodes: all ties."""
    t = Pod(name="t", requests=Resources.make(cpu="500m", memory="512Mi"))
    return _nodes(8, "4", "8Gi", 110), [], [replica(t, i) for i in range(24)]


def capacity_exhaustion():
    """More demand than the cluster holds (7.8 of 6 CPU; 3 pods a node):
    nodes fill one by one and the tail fails."""
    big = Pod(name="t", requests=Resources.make(cpu="900m", memory="900Mi"))
    small = Pod(name="s", requests=Resources.make(cpu="300m", memory="100Mi"))
    return (_nodes(3, "2", "2Gi", 3), [],
            [replica(big, i) for i in range(6)]
            + [replica(small, 10 + i) for i in range(8)])


def golden_burst(seed):
    """Randomized cluster (affinity, anti-affinity, spread, taints, ports,
    volumes) whose pending pods are template-stamped bursts of 1-6."""
    rng = random.Random(3000 + seed)
    nodes = [rand_node(rng, i) for i in range(rng.randint(3, 7))]
    existing = [rand_pod(rng, 100 + i, bound_to=rng.choice(nodes).name)
                for i in range(rng.randint(0, 5))]
    pending = []
    i = 0
    while len(pending) < 18:
        t = rand_pod(rng, i)
        for _ in range(rng.randint(1, 6)):
            pending.append(replica(t, i))
            i += 1
    return nodes, existing, pending


def priority_tiers():
    """Two deployments at distinct priorities interleaved by creation:
    queue order re-groups them into two blocks."""
    lo = Pod(name="lo", requests=Resources.make(cpu="250m", memory="256Mi"),
             priority=0)
    hi = Pod(name="hi", requests=Resources.make(cpu="500m", memory="512Mi"),
             priority=5)
    return (_nodes(4, "4", "8Gi", 10), [],
            [replica(hi if i % 2 else lo, i) for i in range(12)])


def self_anti_affinity_zero_slack():
    """Self-anti-affine replicas, one per hostname, MORE replicas (6) than
    nodes (4): the overflow fails."""
    sel = LabelSelector.of(match_labels={"app": "db"})
    t = Pod(name="t", labels={"app": "db"},
            requests=Resources.make(cpu="100m", memory="64Mi"),
            affinity=Affinity(anti_required=(
                PodAffinityTerm(selector=sel, topology_key=HOSTNAME),)))
    return (_nodes(4, "8", "16Gi", 110, hostname=True), [],
            [replica(t, i) for i in range(6)])


def port_conflict():
    """Host-port replicas: one a node, 5 replicas on 3 nodes."""
    t = Pod(name="t", requests=Resources.make(cpu="100m", memory="64Mi"),
            host_ports=(HostPort(8080, "TCP", ""),))
    return _nodes(3, "8", "16Gi", 110), [], [replica(t, i) for i in range(5)]


def soft_affinity_weight_flow():
    """A class with preferred affinity toward ANOTHER class: its placements
    write symmetric soft-affinity weight (WSYM) that the later class's
    scores read."""
    web_sel = LabelSelector.of(match_labels={"app": "web"})
    existing = [Pod(name=f"w{i}", labels={"app": "web"},
                    requests=Resources.make(cpu="100m", memory="64Mi"),
                    node_name=f"n{i % 2}", creation_index=i)
                for i in range(2)]
    puller = Pod(
        name="t", labels={"app": "cache"},
        requests=Resources.make(cpu="100m", memory="64Mi"),
        affinity=Affinity(pod_preferred=(
            WeightedPodAffinityTerm(
                weight=37,
                term=PodAffinityTerm(selector=web_sel,
                                     topology_key=HOSTNAME)),)))
    web = Pod(name="t2", labels={"app": "web"},
              requests=Resources.make(cpu="150m", memory="96Mi"))
    return (_nodes(5, "8", "16Gi", 110, hostname=True), existing,
            [replica(puller, i) for i in range(6)]
            + [dataclasses.replace(web, name=f"q{i}", creation_index=10 + i)
               for i in range(4)])


def rw_volume():
    """Replicas sharing a read-write volume conflict with themselves on a
    node (NoDiskConflict): one a node, 5 replicas on 3 nodes."""
    t = Pod(name="t", requests=Resources.make(cpu="100m", memory="64Mi"),
            volumes=(VolumeRef(vol_id="shared", driver="pd",
                               read_only=False),))
    return _nodes(3, "8", "16Gi", 110), [], [replica(t, i) for i in range(5)]


def nodename_pin_mid_burst():
    """spec.nodeName on pods 3 and 4 of an 8-replica burst, both to n2."""
    t = Pod(name="t", requests=Resources.make(cpu="250m", memory="256Mi"))
    pods = [replica(t, i) for i in range(8)]
    for i in (3, 4):
        pods[i] = dataclasses.replace(pods[i], node_name="n2")
    return _nodes(4, "4", "8Gi", 110), [], pods


SCENARIOS = {
    "homogeneous-spread": homogeneous_spread,
    "capacity-exhaustion": capacity_exhaustion,
    **{f"golden-burst-{s}": functools.partial(golden_burst, s)
       for s in range(6)},
    "priority-tiers": priority_tiers,
    "self-anti-affinity-zero-slack": self_anti_affinity_zero_slack,
    "port-conflict": port_conflict,
    "soft-affinity-weight-flow": soft_affinity_weight_flow,
    "rw-volume": rw_volume,
}

PLACED = {
    "homogeneous-spread": 24,
    "priority-tiers": 12,
    "self-anti-affinity-zero-slack": 4,
    "port-conflict": 3,
    "soft-affinity-weight-flow": 10,
    "rw-volume": 3,
}
