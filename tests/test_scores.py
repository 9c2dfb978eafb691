"""Golden SCORE tests: the device score matrix vs the pure-Python oracle.

The reference unit-tests each priority function with fixed tables
(algorithm/priorities/*_test.go); here the full composed score surface —
preferred node affinity, taints, least/balanced allocation, preferred
inter-pod affinity INCLUDING the symmetric existing-pod pass, EvenPodsSpread
ScheduleAnyway score, SelectorSpread (host+zone), ImageLocality — is compared
against api/semantics.py on randomized clusters, feasible entries only.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_tpu.api import semantics as sem
from kubernetes_tpu.api.types import (
    Affinity,
    LabelSelector,
    Node,
    Pod,
    PodAffinityTerm,
    Resources,
    Taint,
    TaintEffect,
    TopologySpreadConstraint,
    UnsatisfiableAction,
    WeightedPodAffinityTerm,
)
from kubernetes_tpu.sched.cycle import UNSCHEDULABLE_TAINT_KEY, _scores
from kubernetes_tpu.state.dims import Dims
from kubernetes_tpu.state.encode import Encoder

ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
APPS = ["web", "db", "cache", "queue"]
IMAGES = [("registry/app:v1", 50 * 1024), ("registry/db:v2", 400 * 1024),
          ("registry/tiny:v1", 8 * 1024), ("registry/big:v3", 900 * 1024)]


def rand_node(rng, i):
    labels = {HOSTNAME: f"n{i}"}
    if rng.random() < 0.8:
        labels[ZONE] = f"z{rng.randrange(3)}"
    images = {}
    for name, size in IMAGES:
        if rng.random() < 0.5:
            images[name] = size
    taints = ()
    if rng.random() < 0.3:
        taints = (Taint("dedicated", "x", TaintEffect.PREFER_NO_SCHEDULE),)
    return Node(name=f"n{i}", labels=labels,
                allocatable=Resources.make(cpu=rng.choice(["2", "4"]),
                                           memory="8Gi", pods=50),
                taints=taints, images_kib=images)


def rand_pod(rng, i, bound_to=None):
    app = rng.choice(APPS)
    sel = LabelSelector.of(match_labels={"app": rng.choice(APPS)})
    paff = panti = ()
    if rng.random() < 0.5:
        paff = (WeightedPodAffinityTerm(
            term=PodAffinityTerm(selector=sel, topology_key=ZONE),
            weight=rng.randrange(1, 100)),)
    if rng.random() < 0.4:
        panti = (WeightedPodAffinityTerm(
            term=PodAffinityTerm(
                selector=LabelSelector.of(match_labels={"app": rng.choice(APPS)}),
                topology_key=rng.choice([ZONE, HOSTNAME])),
            weight=rng.randrange(1, 100)),)
    aff_req = ()
    if bound_to and rng.random() < 0.3:
        aff_req = (PodAffinityTerm(
            selector=LabelSelector.of(match_labels={"app": rng.choice(APPS)}),
            topology_key=ZONE),)
    spread = ()
    if rng.random() < 0.5:
        spread = (TopologySpreadConstraint(
            max_skew=1, topology_key=ZONE,
            when_unsatisfiable=UnsatisfiableAction.SCHEDULE_ANYWAY,
            selector=LabelSelector.of(match_labels={"app": app})),)
    ssel = ()
    if rng.random() < 0.5:
        ssel = (LabelSelector.of(match_labels={"app": app}),)
    images = tuple(nm for nm, _ in IMAGES if rng.random() < 0.5)
    return Pod(
        name=f"p{i}", labels={"app": app},
        requests=Resources.make(cpu=rng.choice(["100m", "500m"]),
                                memory=rng.choice(["128Mi", "1Gi"])),
        affinity=Affinity(pod_required=aff_req, pod_preferred=paff,
                          anti_preferred=panti),
        topology_spread=spread,
        spread_selectors=ssel,
        images=images,
        node_name=bound_to or "",
        creation_index=i,
    )


def oracle_score(pod, node, nodes, existing, used_by_node):
    """Float composition mirroring the engine's score row exactly."""
    used, used_pods = used_by_node[node.name]

    def least(reqv, usedv, capv):
        total = usedv + reqv
        if capv == 0 or total > capv:
            return 0.0
        return (capv - total) * 100.0 / capv

    least_s = (least(pod.requests.milli_cpu, used.milli_cpu,
                     node.allocatable.milli_cpu)
               + least(pod.requests.memory_kib, used.memory_kib,
                       node.allocatable.memory_kib)) / 2.0

    def frac(total, cap):
        return total / cap if cap else 1.0

    cf = frac(used.milli_cpu + pod.requests.milli_cpu,
              node.allocatable.milli_cpu)
    mf = frac(used.memory_kib + pod.requests.memory_kib,
              node.allocatable.memory_kib)
    balanced = 0.0 if (cf >= 1 or mf >= 1) else 100.0 - abs(cf - mf) * 100.0

    # preferred node affinity: none in this workload → contributes 0
    # taint PreferNoSchedule: reversed max-normalized over nodes
    counts = {n.name: sem.taint_toleration_score(pod, n) for n in nodes}
    mx = max(counts.values())
    taint_s = 100.0 * (1.0 - counts[node.name] / mx) if mx > 0 else 100.0

    soft_ip = sem.interpod_preferred_scores(pod, nodes, existing)[node.name]
    even_soft = sem.even_spread_soft_scores(pod, nodes, existing)[node.name]
    ssel = sem.selector_spread_scores(pod, nodes, existing)[node.name]
    img = sem.image_locality_scores(pod, nodes)[node.name]
    return least_s + balanced + taint_s + soft_ip + even_soft + ssel + img


def _encode(nodes, existing, pending, E):
    base = Dims(N=8, P=8, E=E, R=8, SC=64, S=64, SR=64, SL=64, SN=32, D=8,
                PAT=2, PAN=2, TS=2, SS=2, CI=4, IMG=8, K=4)
    enc = Encoder()
    enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
    enc.vocabs.label_vals.intern("")
    tables, ex, pe, d = enc.encode_cluster(nodes, existing, pending, base)
    uk = jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY))
    ev = jnp.int32(enc.vocabs.label_vals.get(""))
    return tables, ex, pe, d, uk, ev


@pytest.mark.parametrize("seed", range(8))
def test_score_matrix_matches_oracle(seed):
    rng = random.Random(1000 + seed)
    n_nodes = rng.randint(3, 6)
    nodes = [rand_node(rng, i) for i in range(n_nodes)]
    existing = [rand_pod(rng, 100 + i, bound_to=rng.choice(nodes).name)
                for i in range(rng.randint(0, 8))]
    pending = [rand_pod(rng, i) for i in range(rng.randint(1, 6))]

    tables, ex, pe, d, uk, ev = _encode(nodes, existing, pending, E=16)
    got = np.asarray(_scores(jax.device_put(tables), jax.device_put(pe),
                             (uk, ev), d.D, jax.device_put(ex)))

    used_by_node = {}
    for n in nodes:
        agg = Resources()
        cnt = 0
        cpu = mem = 0
        for exp in existing:
            if exp.node_name == n.name:
                cpu += exp.requests.milli_cpu
                mem += exp.requests.memory_kib
                cnt += 1
        used_by_node[n.name] = (Resources(milli_cpu=cpu, memory_kib=mem), cnt)

    for pi, pod in enumerate(pending):
        for ni, node in enumerate(nodes):
            if got[pi, ni] == -np.inf:
                continue  # infeasible — covered by the filter golden tests
            want = oracle_score(pod, node, nodes, existing, used_by_node)
            assert abs(got[pi, ni] - want) < 0.05, (
                f"seed={seed} pod={pod.name} node={node.name}: "
                f"device={got[pi, ni]:.4f} oracle={want:.4f}\n"
                f"pod={pod}")


def _seed_cluster(rng, n_hot):
    """Existing pods with every kind of term that reaches a seed: preferred
    (anti-)affinity (± weights in WCOLS), required affinity (hard weight),
    required anti-affinity (HOLD) — and `n_hot` replicas of one class on n0."""
    nodes = [rand_node(rng, i) for i in range(5)]
    existing = [rand_pod(rng, 1000 + i, bound_to=rng.choice(nodes).name)
                for i in range(40)]
    for p in existing[::3]:
        p.affinity = Affinity(
            pod_required=p.affinity.pod_required,
            pod_preferred=p.affinity.pod_preferred,
            anti_preferred=p.affinity.anti_preferred,
            anti_required=(PodAffinityTerm(
                selector=LabelSelector.of(
                    match_labels={"app": rng.choice(APPS)}),
                topology_key=HOSTNAME),))
    hot_sel = LabelSelector.of(match_labels={"app": "web"})
    for i in range(n_hot):
        existing.append(Pod(
            name=f"hot{i}", labels={"app": "web"},
            requests=Resources.make(cpu="1m", memory="1Mi"),
            affinity=Affinity(
                anti_preferred=(WeightedPodAffinityTerm(
                    term=PodAffinityTerm(selector=hot_sel, topology_key=ZONE),
                    weight=97),),
                anti_required=(PodAffinityTerm(
                    selector=LabelSelector.of(match_labels={"app": "db"}),
                    topology_key=HOSTNAME),)),
            node_name="n0", creation_index=2000 + i))
    return nodes, existing


@pytest.mark.parametrize("case", ["as-encoded", "over-256-on-one-node",
                                  "rows-tampered", "tampered-over-256"])
def test_cycle_seeds_match_numpy_loop(case):
    """CNT, HOLD and WSYM — products against the class × node histogram
    (interpod.class_node_hist) — equal a plain loop over the existing pods,
    bit for bit: with pods on nodes, unbound pods, invalid rows, cls -1
    (counted under class 0, as every class gather does), a node-term count
    above 256 (where a bf16 product would round) and negative weights."""
    from kubernetes_tpu.ops.lattice import build_cycle

    nodes, existing = _seed_cluster(random.Random(case),
                                    n_hot=301 if "256" in case else 7)
    tables, ex, _pe, d, uk, ev = _encode(nodes, existing, [], E=512)
    valid, cls, node_id = (np.array(a) for a in (ex.valid, ex.cls,
                                                 ex.node_id))
    if "tampered" in case:
        pick = np.random.default_rng(7).permutation(np.flatnonzero(valid))
        valid[pick[:9]] = False          # invalid rows that keep cls / node
        node_id[pick[9:18]] = -1         # unbound
        cls[pick[18:24]] = -1            # classless
        ex = ex._replace(valid=valid, cls=cls, node_id=node_id)
    cyc = jax.jit(build_cycle, static_argnums=(4,))(
        jax.device_put(tables), jax.device_put(ex), uk, ev, d.D)
    TM, has_anti, WCOLS = (np.asarray(a) for a in (cyc.TM, cyc.has_anti,
                                                   cyc.WCOLS))
    S, N = TM.shape[0], np.asarray(tables.nodes.valid).shape[0]
    CNT = np.zeros((S, N), np.int32)
    HOLD = np.zeros((S, N), np.int32)
    WSYM = np.zeros((S, N), np.float32)
    for e in range(valid.shape[0]):
        if valid[e] and node_id[e] >= 0:
            c, n = max(int(cls[e]), 0), int(node_id[e])
            CNT[:, n] += TM[:, c]
            HOLD[:, n] += has_anti[c, :]
            WSYM[:, n] += WCOLS[:, c]
    assert (WCOLS < 0).any() and (WCOLS > 0).any() and HOLD.any()
    if "256" in case:
        assert CNT.max() > 256 and HOLD.max() > 256
        assert np.abs(WSYM).max() > 256 * 97 - 1
    for name, got, want in (("CNT", cyc.CNT, CNT), ("HOLD", cyc.HOLD, HOLD),
                            ("WSYM", cyc.WSYM, WSYM)):
        got = np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want), (
            f"{case}: {name} differs at {np.argwhere(got != want)[:5]}")
