"""Golden SCORE tests: the device score matrix vs the pure-Python oracle.

The reference unit-tests each priority function with fixed tables
(algorithm/priorities/*_test.go); here the full composed score surface —
preferred node affinity, taints, least/balanced allocation, preferred
inter-pod affinity INCLUDING the symmetric existing-pod pass, EvenPodsSpread
ScheduleAnyway score, SelectorSpread (host+zone), ImageLocality — is compared
against api/semantics.py on randomized clusters, feasible entries only.
"""

import dataclasses
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


# --------------------------------------------------------------------------- #
# the [S, N] in-domain count table (ops/interpod.py term_domain_counts): what
# required and preferred pod (anti-)affinity read of a state, once per TERM;
# every class selects its slots' rows where a program evaluates many classes
# against one state ("term"), and aggregates its own slots where it evaluates
# few ("row"). Integer counts: the two are bit-equal.
# --------------------------------------------------------------------------- #

def _req(app, key=ZONE):
    return PodAffinityTerm(
        selector=LabelSelector.of(match_labels={"app": app}),
        topology_key=key)


def _plain_pod(name, app, i, node="", **aff):
    return Pod(name=name, labels={"app": app},
               requests=Resources.make(cpu="10m", memory="16Mi"),
               affinity=Affinity(**aff), node_name=node, creation_index=i)


def _table_cluster(case):
    """(nodes, existing, pending) for one parametrised case."""
    from kubernetes_tpu.models.workloads import (
        density_pods, flagship_pods, make_nodes)

    rng = random.Random(case)
    if case == "flagship-roles":
        # spread / host anti-affinity / in-zone affinity to the anti partner
        nodes = make_nodes(16, zones=4, racks_per_zone=2)
        pods = flagship_pods(96, groups=6)
        for i, p in enumerate(pods[48:]):
            p.node_name = nodes[(i * 4) % 16].name      # zone-0 hosts only
        return nodes, pods[48:], pods[:48]
    if case == "no-term":
        nodes = make_nodes(8, zones=2, racks_per_zone=2)
        pods = density_pods(40, groups=5)
        for i, p in enumerate(pods[20:]):
            p.node_name = nodes[i % 8].name
        return nodes, pods[20:], pods[:20]
    if case == "over-256-on-one-domain":
        nodes, existing = _seed_cluster(rng, n_hot=301)
        pending = [_plain_pod(f"q{i}", "db", i,
                              pod_required=(_req("web", HOSTNAME),),
                              anti_required=(_req("db", HOSTNAME),))
                   for i in range(3)]
        return nodes, existing, pending + [rand_pod(rng, 50 + i)
                                           for i in range(8)]
    if case == "key-missing-on-some-nodes":
        nodes = [rand_node(rng, i) for i in range(8)]
        nodes[0].labels.pop(ZONE, None)
        nodes[1].labels[ZONE] = "z0"
        existing = [rand_pod(rng, 100 + i, bound_to=rng.choice(nodes).name)
                    for i in range(40)]
        return nodes, existing, [rand_pod(rng, i, bound_to=None)
                                 for i in range(16)]
    nodes = [Node(name=f"n{i}",
                  labels={HOSTNAME: f"n{i}", ZONE: f"z{i % 3}"},
                  allocatable=Resources.make(cpu="4", memory="8Gi", pods=50))
             for i in range(6)]
    existing = [_plain_pod(f"e{i}", APPS[i % 4], 100 + i,
                           node=f"n{(i * 5) % 6}") for i in range(14)]
    if case == "slots-half-padded":
        # AT = 2: classes with one required term leave a -1 slot, classes
        # with two fill both; one anti term on some (AN = 1)
        pending = [_plain_pod(f"p{i}", APPS[i % 4], i,
                              pod_required=(_req(APPS[(i + 1) % 4]),)
                              + ((_req(APPS[(i + 2) % 4], HOSTNAME),)
                                 if i % 2 else ()),
                              anti_required=(_req("queue", HOSTNAME),)
                              if i % 3 == 0 else ())
                   for i in range(8)]
    elif case == "two-classes-share-a-term":
        # different labels -> different classes; the SAME (selector,
        # namespaces, key) -> one interned term both select
        pending = [_plain_pod(f"p{i}", APPS[i % 2], i,
                              pod_required=(_req("cache", HOSTNAME),),
                              pod_preferred=(WeightedPodAffinityTerm(
                                  term=_req("cache", HOSTNAME),
                                  weight=10 + i),))
                   for i in range(4)]
    elif case == "first-pod-escape":
        # "solo": no pod matches anywhere (total 0) and the class matches its
        # own term -> passes everywhere. "keyless": the only matching pod
        # sits on a node WITHOUT the key, which the total must not count
        nodes.append(Node(name="bare", labels={HOSTNAME: "bare"},
                          allocatable=Resources.make(cpu="4", memory="8Gi",
                                                     pods=50)))
        existing.append(_plain_pod("lost", "keyless", 200, node="bare"))
        pending = [_plain_pod("p0", "solo", 0,
                              pod_required=(_req("solo"),)),
                   _plain_pod("p1", "keyless", 1,
                              pod_required=(_req("keyless"),)),
                   _plain_pod("p2", "web", 2,
                              pod_required=(_req("nobody"),)),
                   _plain_pod("p3", "db", 3,
                              pod_required=(_req("cache"),))]
    else:
        raise AssertionError(case)
    return nodes, existing, pending


_TABLE_CASES = ["flagship-roles", "no-term", "slots-half-padded",
                "two-classes-share-a-term", "key-missing-on-some-nodes",
                "first-pod-escape", "over-256-on-one-domain"]


def _table_state(case):
    from kubernetes_tpu.ops.lattice import build_cycle

    nodes, existing, pending = _table_cluster(case)
    tables, ex, pe, d, uk, ev = _encode(nodes, existing, pending, E=512)
    tables, ex, pe = (jax.device_put(x) for x in (tables, ex, pe))
    cyc = jax.jit(build_cycle, static_argnums=(4,))(tables, ex, uk, ev, d.D)
    return tables, cyc, pe, d


@pytest.mark.parametrize("case", _TABLE_CASES)
def test_term_table_matches_numpy_loop(case):
    """SEG [S, D+1], the table's cnt [S, N] and tot [S] against a plain loop
    over terms and nodes, bit for bit."""
    from kubernetes_tpu.ops.interpod import (
        domain_agg, domain_of_term, term_domain_counts)

    tables, cyc, _pe, d = _table_state(case)

    assert d.domain_sum("waves") == "product" and cyc.SAME is not None

    @jax.jit
    def device(t, cyc):
        dom, _ = domain_of_term(t.nodes, t.terms.topo_key)
        return (domain_agg(cyc.CNT, dom, d.D),
                term_domain_counts(t.terms, cyc.CNT, cyc.HOLD, cyc.WSYM,
                                   t.nodes, d.D, cyc.SAME))

    seg, table = jax.tree.map(np.asarray, device(tables, cyc))
    CNT = np.asarray(cyc.CNT)
    key = np.asarray(tables.terms.topo_key)
    domain = np.asarray(tables.nodes.domain)
    valid = np.asarray(tables.nodes.valid)
    S, N = CNT.shape
    SEG = np.zeros((S, d.D), np.int32)
    DCNT = np.zeros((S, N), np.int32)
    TOT = np.zeros((S,), np.int32)
    for s in range(S):
        on_key = [n for n in range(N) if key[s] >= 0 and valid[n]
                  and domain[n, key[s]] >= 0]
        for n in on_key:
            SEG[s, domain[n, key[s]]] += CNT[s, n]
            TOT[s] += CNT[s, n]
        for n in on_key:
            DCNT[s, n] = SEG[s, domain[n, key[s]]]
    assert np.array_equal(seg[:, :d.D], SEG)
    assert table.cnt.dtype == np.int32 and np.array_equal(table.cnt, DCNT)
    assert np.array_equal(table.tot, TOT)
    if case == "no-term":
        assert not (key >= 0).any() and not DCNT.any()
    else:
        assert DCNT.any() and TOT.any()
    if case == "over-256-on-one-domain":
        assert DCNT.max() > 256
    if case in ("key-missing-on-some-nodes", "first-pod-escape"):
        # a node without the key reads 0 although pods on it match
        keyless = (key[:, None] >= 0) & valid[None, :] \
            & (domain[:, np.maximum(key, 0)].T < 0)
        assert (keyless & (CNT > 0)).any() and not DCNT[keyless].any()


@pytest.mark.parametrize("case", _TABLE_CASES)
def test_affinity_rows_with_table_equal_per_row(case):
    """affinity_rows and soft_affinity_row for EVERY class: selecting from
    the state's table ("term") against aggregating the class's own slots
    ("row"), bit for bit."""
    from kubernetes_tpu.ops.interpod import (
        affinity_rows, soft_affinity_row, term_domain_counts)

    tables, cyc, pe, d = _table_state(case)
    classes, terms, nodes = tables.classes, tables.terms, tables.nodes
    SC = classes.valid.shape[0]

    def rows(table):
        def one(c):
            aff, anti = affinity_rows(c, classes, terms, cyc.TM, cyc.CNT,
                                      cyc.HOLD, nodes, d.D, table, cyc.SAME)
            soft = soft_affinity_row(c, classes, terms, cyc.CNT, nodes, d.D,
                                     TM=cyc.TM, WSYM=cyc.WSYM, table=table,
                                     same=cyc.SAME)
            return aff, anti, soft
        return jax.vmap(one)(jnp.arange(SC))

    per_row = jax.tree.map(np.asarray, jax.jit(lambda: rows(None))())
    from_table = jax.tree.map(np.asarray, jax.jit(lambda: rows(
        term_domain_counts(terms, cyc.CNT, cyc.HOLD, cyc.WSYM, nodes, d.D,
                           cyc.SAME)))())
    for name, a, b in zip(("affinity_ok", "anti_ok", "soft"),
                          per_row, from_table):
        assert a.dtype == b.dtype and np.array_equal(a, b), (case, name)

    aff_ok, anti_ok, soft = per_row
    nv = np.asarray(nodes.valid)
    live = np.asarray(classes.valid)
    has_aff = (np.asarray(classes.aff_terms) >= 0).any(1) & live
    if case == "no-term":
        assert not has_aff.any() and aff_ok.all() and anti_ok[:, nv].all()
    else:
        # the predicates bite: some class is refused somewhere
        assert not aff_ok[has_aff][:, nv].all()
    if case == "slots-half-padded":
        n_slots = (np.asarray(classes.aff_terms)[live] >= 0).sum(1)
        assert {1, 2} <= set(n_slots.tolist())
    if case == "two-classes-share-a-term":
        ats = np.asarray(classes.aff_terms)[has_aff]
        assert len(ats) >= 2 and len({tuple(a) for a in ats}) == 1
        assert soft.any()
    if case == "first-pod-escape":
        # solo and keyless escape (every node passes, the keyless one too);
        # "web" requires a pod nobody is and matches no term of its own: no
        # node; "db" finds a cache pod in every zone: every node with the key
        passing = aff_ok[np.asarray(pe.cls)[:4]][:, nv].sum(1).tolist()
        assert passing == [nv.sum(), nv.sum(), 0, nv.sum() - 1]
    if case in ("flagship-roles", "over-256-on-one-domain"):
        assert not anti_ok[live][:, nv].all()


def test_few_rows_path_equals_many_rows_path():
    """A verb's P = 8 pods aggregate their own slots ("row": 8 rows x 7
    slots under S = 64 terms); the waves round's SC = 64 classes build the
    table ("term"). Same state, same answers: the Filter mask, its
    components and the Score matrix."""
    from kubernetes_tpu.ops import assign

    tables, cyc, pe, d = _table_state("flagship-roles")
    pe = jax.tree.map(lambda a: a[:8], pe)
    assert dataclasses.replace(d, P=8).affinity_agg("extender") == "row"
    assert d.affinity_agg("waves") == "term"
    assert d.affinity_agg("scan") == "row"
    state = assign.initial_state(tables, cyc)
    assert assign.state_affinity_table(tables, cyc, state, 8) is None
    table = assign.state_affinity_table(
        tables, cyc, state, tables.classes.valid.shape[0])
    assert table is not None

    @jax.jit
    def few():
        return (assign.feasible_matrix(tables, cyc, pe),
                assign.score_matrix(tables, cyc, pe),
                assign.mask_components(tables, cyc, pe))

    @jax.jit
    def many():
        def row(c, nnr, v):
            mask = assign.pod_mask_row(tables, cyc, state, c, nnr, v, table)
            score = assign.score_row(tables, cyc, state, c, table)
            return mask, jnp.where(mask, score, -jnp.inf)
        return jax.vmap(row)(pe.cls, pe.node_name_req, pe.valid)

    feas, score, comps = jax.tree.map(np.asarray, few())
    mask_t, score_t = jax.tree.map(np.asarray, many())
    assert np.array_equal(feas, mask_t) and np.array_equal(score, score_t)
    assert feas.any() and not feas.all()
    assert not comps.affinity.all() and not comps.anti.all()


@pytest.mark.parametrize("dims,engine,want", [
    # the flagship's capacities (benchmarks: N 5,120, SC 64, S 72)
    (dict(SC=64, S=72, P=53248), "waves", "term"),
    (dict(SC=64, S=72, P=8), "extender", "row"),
    (dict(SC=64, S=72, P=53248), "scan", "row"),
    # density-1k: S 8, nothing to aggregate either way
    (dict(SC=64, S=8, P=30720), "waves", "term"),
    # more terms than the round's classes would ask for: rows stay cheaper
    (dict(SC=8, S=64, P=8), "waves", "row"),
    # a fleet tick's record is not one program's: each tenant group's engine
    (dict(SC=64, S=72, P=53248), "fleet", None),
])
def test_affinity_agg_is_chosen_from_dims(dims, engine, want):
    """rows x (AT + AN + PAT + PAN) aggregates against S: the choice the
    flight recorder reports beside `bucket`."""
    assert Dims(**dims).affinity_agg(engine) == want


# --------------------------------------------------------------------------- #
# the in-domain sum's two forms (ops/interpod.py in_domain_sums; ISSUE 42):
# rows times the key's same-domain matrix on the MXU ("product") against the
# scatter-add into [A, D+1] and the gather back ("scatter"), bit for bit
# --------------------------------------------------------------------------- #

_SUM_CASES = ["key-absent-on-some-nodes", "keyless-rows", "invalid-nodes",
              "hostname-key", "sum-past-2^16", "signed-weights",
              "what-if-lanes"]


def _sum_inputs(case, seed):
    """(nodes, D, keys [A], rows [A, N] or [lanes, A, N]) for one hard case;
    every case keeps the others' features at a lower dose."""
    from types import SimpleNamespace

    rng = np.random.default_rng(seed)
    A, N, K = 12, 96, 4
    absent = 0.5 if case == "key-absent-on-some-nodes" else 0.1
    dom = np.stack([rng.integers(0, 4, N), rng.integers(0, 12, N),
                    rng.permutation(N), rng.integers(0, 3, N)], 1)
    dom = np.where(rng.random((N, K)) < absent, -1, dom)
    dom[:, 2] = np.arange(N)           # hostname: a domain a node, D = N
    valid = rng.random(N) > (0.4 if case == "invalid-nodes" else 0.05)
    keys = rng.integers(-1 if case != "keyless-rows" else -3, K, A)
    keys = np.maximum(keys, -1)
    if case == "hostname-key":
        keys[:] = 2
    hi = 9000 if case == "sum-past-2^16" else 40
    rows = rng.integers(0, hi, (A, N))
    dtype = np.int32
    if case == "signed-weights":       # WSYM: integer-valued f32, signed
        rows, dtype = rng.integers(-100 * 40, 100 * 40, (A, N)), np.float32
    if case == "what-if-lanes":        # a lane's own [S, N] survivors' counts
        rows = rng.integers(0, hi, (8, A, N))
    nodes = SimpleNamespace(domain=jnp.asarray(dom, jnp.int32),
                            valid=jnp.asarray(valid))
    return nodes, N, jnp.asarray(keys, jnp.int32), jnp.asarray(rows, dtype)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("case", _SUM_CASES)
def test_domain_product_equals_scatter(case, seed):
    """The product form against the scatter form AND a plain loop over terms
    and nodes, element for element, on the cases that could tell them apart:
    a key absent on some nodes, rows with no key, invalid nodes, a hostname
    key (D = N), a domain whose sum passes 2^16 (three bf16 digits), signed
    f32 weights, and the what-if's lane-batched rows (a vmap over lanes)."""
    from kubernetes_tpu.ops.interpod import in_domain_sums, same_domain

    nodes, D, keys, rows = _sum_inputs(case, seed)
    same = same_domain(nodes)
    assert same.dtype == jnp.bfloat16 and same.shape == (4, D, D)

    def both(r):
        return (in_domain_sums(r, keys, nodes, D, same),
                in_domain_sums(r, keys, nodes, D))

    f = jax.vmap(both) if rows.ndim == 3 else both
    product, scatter = jax.tree.map(np.asarray, jax.jit(f)(rows))
    assert product.dtype == scatter.dtype == rows.dtype
    np.testing.assert_array_equal(product, scatter)

    dom = np.where(np.asarray(nodes.valid)[:, None],
                   np.asarray(nodes.domain), -1)
    r = np.asarray(rows).reshape((-1,) + rows.shape[-2:]).astype(np.int64)
    want = np.zeros_like(r)
    for a, k in enumerate(np.asarray(keys)):
        if k < 0:
            continue
        for n in np.flatnonzero(dom[:, k] >= 0):
            want[:, a, n] = r[:, a, dom[:, k] == dom[n, k]].sum(-1)
    np.testing.assert_array_equal(product.reshape(want.shape), want)
    assert want.any()
    if case == "sum-past-2^16":
        assert want.max() > 2 ** 16
    if case == "signed-weights":
        assert want.min() < 0 < want.max()
    if case == "keyless-rows":
        assert (np.asarray(keys) < 0).sum() >= 2


@pytest.mark.parametrize("case", _TABLE_CASES)
def test_program_with_product_equals_program_with_scatter(case):
    """One state's Filter mask, its components and the Score matrix, with
    the cycle's same-domain matrices (what build_cycle carries at these
    shapes) against the same cycle without them (the scatter form), and the
    state's table in both forms: bit for bit."""
    from kubernetes_tpu.ops import assign

    tables, cyc, pe, d = _table_state(case)
    assert cyc.SAME is not None

    @jax.jit
    def run(cyc):
        state = assign.initial_state(tables, cyc)
        return (assign.feasible_matrix(tables, cyc, pe),
                assign.score_matrix(tables, cyc, pe),
                assign.mask_components(tables, cyc, pe),
                assign.state_affinity_table(
                    tables, cyc, state, tables.classes.valid.shape[0]))

    product = jax.tree.map(np.asarray, run(cyc))
    scatter = jax.tree.map(np.asarray, run(cyc._replace(SAME=None)))
    for a, b in zip(jax.tree.leaves(product), jax.tree.leaves(scatter)):
        assert a.dtype == b.dtype and np.array_equal(a, b), case
    table = product[3]
    if case != "no-term":
        assert table.cnt.any() and product[0].any()
    if case == "flagship-roles":
        assert table.hold.any() and table.sym.any()


@pytest.mark.parametrize("N,K,copies,want", [
    # the benchmark's node axes: 210 MB and 8 MB of matrices
    (5120, 4, 1, "product"), (1024, 4, 1, "product"), (8, 4, 1, "product"),
    # the last bucket whose four matrices fit 2 GiB, and the next one up
    (16384, 4, 1, "product"), (18432, 4, 1, "scatter"),
    (53248, 4, 1, "scatter"),
    # more keys, or a fleet tick's stacked tenants, take the room sooner
    (16384, 8, 1, "scatter"), (5120, 4, 16, "scatter"),
    (1024, 4, 16, "product"),
])
def test_domain_sum_is_chosen_from_shapes(N, K, copies, want):
    """copies x K x N x N x 2 bytes against DOMAIN_SUM_MAX_BYTES: the rule
    build_cycle applies and the flight recorder reports; rows do not enter
    (state/dims.py domain_sum's docstring has the arithmetic)."""
    from kubernetes_tpu.state.dims import domain_sum

    assert domain_sum(N, K, copies) == want
    if copies == 1:
        d = Dims(N=N, K=K)
        assert d.domain_sum("waves") == d.domain_sum("extender") \
            == d.domain_sum("scan") == want
        assert d.domain_sum("fleet") is None


def test_build_cycle_carries_the_matrices_only_where_the_rule_says(
        monkeypatch):
    from kubernetes_tpu.ops.lattice import build_cycle
    from kubernetes_tpu.state import dims as dims_mod

    nodes, existing, pending = _table_cluster("flagship-roles")
    tables, ex, _pe, d, uk, ev = _encode(nodes, existing, pending, E=512)

    def shapes():
        return jax.eval_shape(
            lambda t, e: build_cycle(t, e, uk, ev, d.D), tables, ex)

    cyc = shapes()
    assert cyc.SAME.shape == (d.K, d.N, d.N)
    assert cyc.SAME.dtype == jnp.bfloat16
    monkeypatch.setattr(dims_mod, "DOMAIN_SUM_MAX_BYTES",
                        d.K * d.N * d.N * 2 - 1)
    assert shapes().SAME is None and d.domain_sum("waves") == "scatter"


# --------------------------------------------------------------------------- #
# topology spread's eligible-masked in-domain count (ops/topospread.py
# spread_counts, eligible_in_domain; ISSUE 43): one sum per (class, slot) and
# state, under either form of the in-domain sum, shared by the Filter row,
# the soft score and the waves round's cap. Held to a plain numpy loop over
# nodes and domains and to the parent's forms (tests/spread_parent_forms.py).
# --------------------------------------------------------------------------- #

_SPREAD_CASES = ["key-absent-on-some-nodes", "hostname-key",
                 "class-with-no-eligible-node",
                 "eligible-nodes-all-lack-the-key", "invalid-nodes",
                 "counts-past-2^16"]
_I32_MAX = int(np.iinfo(np.int32).max)


def _spread_cluster(case):
    """12 nodes (11 for `invalid-nodes`: N pads to 16) in three zones and two
    pools, 60 bound pods spread unevenly, and one pending pod per (app, pool
    choice): hard spread over one key and soft spread over the other, so
    every class fills both constraint slots."""
    rng = random.Random(case)
    n = 11 if case == "invalid-nodes" else 12
    nodes = [Node(name=f"n{i}",
                  labels={HOSTNAME: f"n{i}", ZONE: f"z{i % 3}",
                          "pool": "ab"[i % 2]},
                  allocatable=Resources.make(cpu="64", memory="256Gi",
                                             pods=500))
             for i in range(n)]
    if case in ("key-absent-on-some-nodes", "eligible-nodes-all-lack-the-key",
                "counts-past-2^16"):
        for i in (0, 5, 7):     # matching pods sit on these all the same
            nodes[i].labels.pop(ZONE)
            nodes[i].labels["pool"] = "bare"
    existing = [_plain_pod(f"e{i}", APPS[(i * i) % 4], 100 + i,
                           node=nodes[(i * 7 + i // 5) % n].name)
                for i in range(60)]
    hard_key, soft_key = (HOSTNAME, ZONE) if case == "hostname-key" \
        else (ZONE, HOSTNAME)
    pools = {"class-with-no-eligible-node": "nowhere",
             "eligible-nodes-all-lack-the-key": "bare"}.get(case, "a")
    pending = []
    for i, app in enumerate(APPS):
        for j, sel in enumerate(({}, {"pool": pools})):
            spread = (
                TopologySpreadConstraint(
                    max_skew=1 + (i + j) % 2, topology_key=hard_key,
                    when_unsatisfiable=UnsatisfiableAction.DO_NOT_SCHEDULE,
                    selector=LabelSelector.of(match_labels={"app": app})),
                TopologySpreadConstraint(
                    max_skew=1, topology_key=soft_key,
                    when_unsatisfiable=UnsatisfiableAction.SCHEDULE_ANYWAY,
                    selector=LabelSelector.of(
                        match_labels={"app": APPS[(i + 1) % 4]})))
            pending.append(Pod(
                name=f"p{i}-{j}", labels={"app": app}, node_selector=sel,
                requests=Resources.make(cpu="10m", memory="16Mi"),
                topology_spread=spread[:1 + rng.randrange(2)] if j else spread,
                creation_index=2 * i + j))
    return nodes, existing, pending


def _spread_state(case):
    from kubernetes_tpu.ops.lattice import build_cycle

    nodes, existing, pending = _spread_cluster(case)
    tables, ex, pe, d, uk, ev = _encode(nodes, existing, pending, E=512)
    tables, ex, pe = (jax.device_put(x) for x in (tables, ex, pe))
    cyc = jax.jit(build_cycle, static_argnums=(4,))(tables, ex, uk, ev, d.D)
    assert cyc.SAME is not None and cyc.D == d.D
    CNT = cyc.CNT
    if case == "counts-past-2^16":
        CNT = CNT * 30011 + (jnp.arange(CNT.shape[1]) % 7)[None, :]
    return tables, cyc, CNT, pe, d


def _numpy_spread_counts(tables, node_match, CNT, classes_with_slots):
    """(cnt [SC, TS, N], min_cnt [SC, TS], any_eligible [SC, TS]) by loops
    over classes, slots, nodes and a dict of domains."""
    tsc_term = np.asarray(tables.classes.tsc_term)
    tsc_key = np.asarray(tables.classes.tsc_key)
    topo_key = np.asarray(tables.terms.topo_key)
    domain = np.asarray(tables.nodes.domain)
    valid = np.asarray(tables.nodes.valid)
    SC, TS = tsc_term.shape
    N = valid.shape[0]
    cnt = np.zeros((SC, TS, N), np.int64)
    min_cnt = np.full((SC, TS), _I32_MAX, np.int64)
    any_el = np.zeros((SC, TS), bool)
    for c in range(SC):
        for t in range(TS):
            s = max(tsc_term[c, t], 0)
            k = topo_key[s]
            keyed = [m for m in range(N)
                     if k >= 0 and valid[m] and domain[m, k] >= 0]
            seg = {}
            for m in keyed:
                if node_match[c, m]:
                    seg[domain[m, k]] = seg.get(domain[m, k], 0) + CNT[s, m]
            for m in keyed:
                cnt[c, t, m] = seg.get(domain[m, k], 0)
            if tsc_term[c, t] < 0:
                assert tsc_key[c, t] < 0
                continue
            assert tsc_key[c, t] == k
            classes_with_slots.add(c)
            eligible = {domain[m, k] for m in keyed if node_match[c, m]}
            any_el[c, t] = bool(eligible)
            if eligible:
                min_cnt[c, t] = min(seg[d] for d in eligible)
    return cnt, min_cnt, any_el


@pytest.mark.parametrize("case", _SPREAD_CASES)
def test_spread_counts_match_numpy_loop(case):
    """`spread_counts` for every (class, slot) under the product, under the
    scatter form, and each class summing its own rows, all against the numpy
    loop, element for element."""
    from kubernetes_tpu.ops.topospread import spread_counts

    tables, cyc, CNT, _pe, d = _spread_state(case)
    classes, terms, nodes = tables.classes, tables.terms, tables.nodes
    nm = cyc.static.node_match
    SC = classes.valid.shape[0]

    def table(same):
        return spread_counts(jnp.arange(SC), classes, terms, CNT, nm, cyc.ELN,
                             nodes, d.D, same)

    def own(same):
        return jax.vmap(lambda c: spread_counts(
            c, classes, terms, CNT, nm[c], cyc.ELN[c], nodes, d.D, same))(
                jnp.arange(SC))

    got = jax.tree.map(np.asarray, jax.jit(lambda: {
        "product": table(cyc.SAME), "scatter": table(None),
        "own-rows-product": own(cyc.SAME), "own-rows-scatter": own(None)})())
    slotted = set()
    want = _numpy_spread_counts(tables, np.asarray(nm), np.asarray(CNT),
                                slotted)
    for form, sc in got.items():
        assert sc.cnt.dtype == np.int32 and sc.min_cnt.dtype == np.int32
        for name, g, w in zip(sc._fields, sc, want):
            np.testing.assert_array_equal(g, w, f"{form} {name}")
    cnt, min_cnt, any_el = want
    live = sorted(slotted)
    assert len(live) >= 8 and cnt[live].any()
    if case == "class-with-no-eligible-node":
        assert (~any_el[live]).any() and any_el[live].any()
        assert (min_cnt[live][~any_el[live]] == _I32_MAX).all()
    elif case == "eligible-nodes-all-lack-the-key":
        # eligible nodes exist, none carries the zone key: no eligible DOMAIN
        none = [c for c in live if np.asarray(nm)[c].any() and not any_el[c, 0]]
        assert none
    else:
        assert any_el[live, 0].all()
    if case == "counts-past-2^16":
        assert cnt.max() > 2 ** 16 and min_cnt[any_el].max() > 2 ** 16
    if case == "hostname-key":
        assert d.D >= len(np.flatnonzero(np.asarray(nodes.valid)))
    if case == "invalid-nodes":
        assert not np.asarray(nodes.valid).all()
    if case == "key-absent-on-some-nodes":
        keyless = np.asarray(nodes.valid) & (np.asarray(nodes.domain)[
            :, np.asarray(terms.topo_key)[
                np.asarray(classes.tsc_term)[live[0], 0]]] < 0)
        assert keyless.sum() == 3 and not cnt[live[0], 0][keyless].any()


@pytest.mark.parametrize("case", _SPREAD_CASES)
def test_eligible_nodes_equal_the_parents_eligible_domains(case):
    """ELN [SC, TS, N] (one in-domain sum of the 0/1 eligibility rows, either
    form) against the parent's ELD [SC, TS, D + 1] scatter-max gathered to
    nodes: node n's domain is eligible, and False where n lacks the key."""
    import spread_parent_forms as parent
    from kubernetes_tpu.ops.topospread import eligible_in_domain

    tables, cyc, _CNT, _pe, d = _spread_state(case)
    classes, nodes = tables.classes, tables.nodes
    nm = cyc.static.node_match
    eld = np.asarray(jax.jit(lambda: parent.eligible_domains(
        nm, classes, nodes, d.D))())
    scatter = np.asarray(jax.jit(lambda: eligible_in_domain(
        nm, classes, nodes, d.D))())
    key = np.asarray(classes.tsc_key)
    dom = np.where(np.asarray(nodes.valid)[:, None],
                   np.asarray(nodes.domain), -1)
    SC, TS = key.shape
    want = np.zeros((SC, TS, dom.shape[0]), bool)
    for c in range(SC):
        for t in range(TS):
            if key[c, t] >= 0:
                dn = dom[:, key[c, t]]
                want[c, t] = (dn >= 0) & eld[c, t, np.maximum(dn, 0)]
    np.testing.assert_array_equal(np.asarray(cyc.ELN), want)
    np.testing.assert_array_equal(scatter, want)
    np.testing.assert_array_equal(want.any(-1), eld[:, :, :d.D].any(-1))
    assert want.any() and not want.all()


@pytest.mark.parametrize("form", ["product-table", "product-own-rows",
                                  "scatter-table", "scatter-own-rows"])
@pytest.mark.parametrize("case", _SPREAD_CASES)
def test_spread_rows_equal_the_parents_forms(case, form):
    """`spread_row` (the Filter mask) and `even_spread_soft_row` (the score)
    for EVERY class, selecting from the state's `SpreadCounts` or summing
    the class's own rows, under either form of the sum: bit for bit the
    parent's rows (its own scatter-add + gather each, ELD's scatter-max)."""
    import spread_parent_forms as parent
    from kubernetes_tpu.ops import assign
    from kubernetes_tpu.ops.scores import even_spread_soft_row
    from kubernetes_tpu.ops.topospread import spread_row

    tables, cyc, CNT, _pe, d = _spread_state(case)
    classes, terms, nodes = tables.classes, tables.terms, tables.nodes
    nm = cyc.static.node_match
    SC = classes.valid.shape[0]
    same = cyc.SAME if form.startswith("product") else None
    cs = jnp.arange(SC)

    @jax.jit
    def new():
        state = assign.initial_state(tables, cyc)._replace(CNT=CNT)
        counts = assign.state_spread_counts(
            tables, cyc._replace(SAME=same), state, SC) \
            if form.endswith("table") else None
        return jax.vmap(lambda c: (
            spread_row(c, classes, terms, cyc.TM, CNT, cyc.ELN, nm[c], nodes,
                       d.D, same, counts),
            even_spread_soft_row(c, classes, terms, CNT, nodes, nm[c], d.D,
                                 same, counts)))(cs)

    @jax.jit
    def old():
        eld = parent.eligible_domains(nm, classes, nodes, d.D)
        return jax.vmap(lambda c: (
            parent.spread_row(c, classes, terms, cyc.TM, CNT, eld, nm[c],
                              nodes, d.D),
            parent.even_spread_soft_row(c, classes, terms, CNT, nodes, nm[c],
                                        d.D)))(cs)

    (mask, soft), (mask0, soft0) = jax.tree.map(np.asarray, (new(), old()))
    np.testing.assert_array_equal(mask, mask0)
    assert soft.dtype == np.float32
    np.testing.assert_array_equal(soft, soft0)
    valid = np.asarray(classes.valid)
    assert soft0[valid].any()
    if case == "class-with-no-eligible-node":
        assert mask0[valid].all(axis=1).any()     # passes everywhere
    assert not mask0[valid].all()                  # and the Filter refuses
