#!/usr/bin/env python
"""chip_smoke.py — the scheduling main path, once, on the TPU, at real size.

One process, no child that needs the chip. It refuses to start unless
`jax.devices()[0].platform == "tpu"` (no override, no CPU mode), then runs

  oracle leg    a small randomized cluster (64 nodes x 256 pods) scheduled on
                the chip and held, placement for placement, to the pure-Python
                oracle kubernetes_tpu/api/semantics.py;
  serving leg   APIServer + Client.local + SchedulerServer (prewarmer,
                supervisor, bind-intent ledger, API preemption attached) over
                5,000 nodes and the 50,000-pod flagship backlog (BASELINE.json
                config 4), all created through the client; then churn cycles
                on the incremental snapshot path, then a preemption burst;
                the final bindings are read back from the apiserver and
                checked by plain host code;
  extender leg  ExtenderServer over an ExtenderBackend holding the same 5,000
                nodes answers real HTTP filter + prioritize requests.

Any failed check, caught phase error, non-zero supervisor counter, non-native
kvstore or array off the TPU gives a non-zero exit. Stdout carries two JSON
lines: the report (sizes, reduced, times, memory, counters, each leg), then,
last, the result and nothing else:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}.
Progress goes to stderr and the full report (every wave, every placement) to
chiprun_out/chip_smoke_{1chip,mesh4,programs}.json.

  python chip_smoke.py              the three legs on one chip
  python chip_smoke.py --mesh 4     the serving leg alone, node axis sharded
                                    over 4 chips
  python chip_smoke.py --programs   builder's census instead of the legs:
                                    AOT-compile the programs the legs do
                                    not reach (scan, explain tail, fleet,
                                    gang) at the flagship shape, and run
                                    the gang program
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import random
import signal
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out")

N_NODES, N_PODS, CHURN = 5000, 50000, 1000
ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
#: supervisor counters that must all end 0 (sched/supervisor.py)
SUPERVISOR_ZERO = ("fallback_dispatches", "degraded_cycles",
                   "watchdog_timeouts", "device_errors", "abandoned",
                   "compile_failures")


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def require_tpu() -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device}")
    if device["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {device}")
    return device


def memory_stats() -> list:
    """Measured per-device peak/limit bytes (device.memory_stats())."""
    import jax

    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append({"id": d.id,
                    "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                    "bytes_limit": st.get("bytes_limit")})
    return out


def platforms_of(tree) -> set:
    import jax

    return {d.platform for a in jax.tree.leaves(tree) for d in a.devices()}


# --------------------------------------------------------------------------- #
# the oracle: kubernetes_tpu/api/semantics.py composed pod-at-a-time
# --------------------------------------------------------------------------- #


def _node_usage(node_name, world):
    from kubernetes_tpu.api.types import Resources

    cpu = mem = eph = count = 0
    scalars: dict = {}
    ports: list = []
    pods: list = []
    for ex in world:
        if ex.node_name != node_name:
            continue
        count += 1
        cpu += ex.requests.milli_cpu
        mem += ex.requests.memory_kib
        eph += ex.requests.ephemeral_kib
        for k, v in ex.requests.scalars:
            scalars[k] = scalars.get(k, 0) + v
        ports.extend(ex.host_ports)
        pods.append(ex)
    used = Resources(milli_cpu=cpu, memory_kib=mem, ephemeral_kib=eph,
                     scalars=tuple(sorted(scalars.items())))
    return used, count, ports, pods


def oracle_fits(pod, node, nodes, world, by_name=None) -> bool:
    """The reference predicate chain (predicates.go predicatesOrdering) for
    one (pod, node) pair against the pods already placed in `world`.
    `by_name` is {node name: node}, for callers that ask many times."""
    from kubernetes_tpu.api import semantics as sem

    used, count, ports, node_pods = _node_usage(node.name, world)
    return (sem.check_node_unschedulable(pod, node)
            and sem.pod_fits_host(pod, node)
            and sem.pod_fits_resources(pod, node, used, count)[0]
            and sem.pod_matches_node_selector(pod, node)
            and sem.pod_fits_host_ports(pod, ports)
            and sem.pod_tolerates_node_taints(pod, node)
            and sem.interpod_affinity_fits(
                pod, node, by_name or {n.name: n for n in nodes}, world)
            and sem.topology_spread_fits(pod, node, nodes, world)
            and sem.no_disk_conflict(pod, node_pods)
            and sem.max_volume_count_fits(pod, node, node_pods))


def oracle_scores(pod, nodes, world) -> dict:
    """node name -> the default provider's weighted score sum, in floats,
    from the oracle's per-priority functions (generic_scheduler.go:714-869)."""
    from kubernetes_tpu.api import semantics as sem

    taints = {n.name: sem.taint_toleration_score(pod, n) for n in nodes}
    mx = max(taints.values())
    soft_ip = sem.interpod_preferred_scores(pod, nodes, world)
    even = sem.even_spread_soft_scores(pod, nodes, world)
    ssel = sem.selector_spread_scores(pod, nodes, world)
    img = sem.image_locality_scores(pod, nodes)
    out = {}
    for n in nodes:
        used, _count, _ports, _pods = _node_usage(n.name, world)

        def least(req, usedv, cap):
            total = usedv + req
            return 0.0 if cap == 0 or total > cap \
                else (cap - total) * 100.0 / cap

        a, r = n.allocatable, pod.requests
        least_s = (least(r.milli_cpu, used.milli_cpu, a.milli_cpu)
                   + least(r.memory_kib, used.memory_kib, a.memory_kib)) / 2.0
        cf = (used.milli_cpu + r.milli_cpu) / a.milli_cpu \
            if a.milli_cpu else 1.0
        mf = (used.memory_kib + r.memory_kib) / a.memory_kib \
            if a.memory_kib else 1.0
        balanced = 0.0 if cf >= 1 or mf >= 1 else 100.0 - abs(cf - mf) * 100.0
        taint_s = 100.0 * (1.0 - taints[n.name] / mx) if mx > 0 else 100.0
        out[n.name] = (least_s + balanced + taint_s + soft_ip[n.name]
                       + even[n.name] + ssel[n.name] + img[n.name])
    return out


def small_cluster(seed: int, n_nodes: int, n_pods: int):
    """A randomized cluster in the golden tests' shape (tests/test_golden.py,
    tests/test_scores.py): zones, taints, images; pods with nodeSelector,
    tolerations, required affinity / anti-affinity, hard and soft spread,
    preferred affinity, SelectorSpread owners — sized so nodes fill up."""
    from kubernetes_tpu.api.types import (
        Affinity, LabelSelector, Node, Pod, PodAffinityTerm, Resources, Taint,
        TaintEffect, Toleration, TolerationOp, TopologySpreadConstraint,
        UnsatisfiableAction, WeightedPodAffinityTerm)

    rng = random.Random(seed)
    apps = ["web", "db", "cache", "queue"]
    images = [("registry/app:v1", 50 * 1024), ("registry/db:v2", 400 * 1024),
              ("registry/big:v3", 900 * 1024)]
    nodes = []
    for i in range(n_nodes):
        labels = {HOSTNAME: f"n{i}", "disk": rng.choice(["ssd", "hdd"])}
        if rng.random() < 0.9:
            labels[ZONE] = f"z{rng.randrange(4)}"
        taints = ()
        roll = rng.random()
        if roll < 0.15:
            taints = (Taint("dedicated", "x", TaintEffect.NO_SCHEDULE),)
        elif roll < 0.35:
            taints = (Taint("dedicated", "x", TaintEffect.PREFER_NO_SCHEDULE),)
        nodes.append(Node(
            name=f"n{i}", labels=labels, taints=taints,
            allocatable=Resources.make(cpu=rng.choice(["1", "2", "4"]),
                                       memory=rng.choice(["2Gi", "4Gi"]),
                                       pods=rng.choice([3, 5, 110])),
            images_kib={nm: sz for nm, sz in images if rng.random() < 0.5},
            unschedulable=rng.random() < 0.05))

    def app_sel():
        return LabelSelector.of(match_labels={"app": rng.choice(apps)})

    def pod(i, bound_to=""):
        app = rng.choice(apps)
        own = LabelSelector.of(match_labels={"app": app})
        required = anti = preferred = anti_pref = ()
        if rng.random() < 0.15:
            required = (PodAffinityTerm(selector=app_sel(),
                                        topology_key=ZONE),)
        if rng.random() < 0.3:
            anti = (PodAffinityTerm(
                selector=own if rng.random() < 0.6 else app_sel(),
                topology_key=rng.choice([HOSTNAME, ZONE])),)
        if rng.random() < 0.4:
            preferred = (WeightedPodAffinityTerm(
                term=PodAffinityTerm(selector=app_sel(), topology_key=ZONE),
                weight=rng.randrange(1, 100)),)
        if rng.random() < 0.3:
            anti_pref = (WeightedPodAffinityTerm(
                term=PodAffinityTerm(
                    selector=app_sel(),
                    topology_key=rng.choice([ZONE, HOSTNAME])),
                weight=rng.randrange(1, 100)),)
        spread = ()
        if rng.random() < 0.5:
            spread = (TopologySpreadConstraint(
                max_skew=rng.randint(1, 2), topology_key=ZONE,
                when_unsatisfiable=rng.choice(list(UnsatisfiableAction)),
                selector=own),)
        tolerations = ()
        if rng.random() < 0.3:
            tolerations = (Toleration(key="dedicated",
                                      op=TolerationOp.EXISTS),)
        return Pod(
            name=f"p{i}", labels={"app": app},
            requests=Resources.make(
                cpu=rng.choice(["100m", "250m", "500m"]),
                memory=rng.choice(["128Mi", "512Mi", "1Gi"])),
            node_selector={"disk": "ssd"} if rng.random() < 0.2 else {},
            affinity=Affinity(pod_required=required, anti_required=anti,
                              pod_preferred=preferred,
                              anti_preferred=anti_pref),
            tolerations=tolerations,
            topology_spread=spread,
            spread_selectors=(own,) if rng.random() < 0.5 else (),
            images=tuple(nm for nm, _ in images if rng.random() < 0.4),
            priority=rng.choice([0, 0, 1, 2]),
            node_name=bound_to, creation_index=i)

    existing = [pod(10_000 + i, bound_to=rng.choice(nodes).name)
                for i in range(max(n_nodes // 4, 1))]
    pending = [pod(i) for i in range(n_pods)]
    return nodes, existing, pending


def oracle_leg(seed: int = 0, n_nodes: int = 64, n_pods: int = 256) -> dict:
    """Schedule the small cluster on the device three ways and hold each to
    the oracle: the Filter mask bit for bit over every (pod, node) pair; the
    sequential `scan` engine pod by pod — unschedulable exactly when the
    oracle finds no node, otherwise on a node the oracle finds feasible whose
    oracle score is the maximum (within the f32 tolerance the score golden
    test uses), the oracle's world then following the placement; and the
    production `waves` engine replayed in (wave, queue) order, every
    placement passing the oracle's predicate chain at its turn."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_tpu.sched.cycle import (
        UNSCHEDULABLE_TAINT_KEY, _feasible, _schedule_batch_impl)
    from kubernetes_tpu.ops.lattice import default_engine_config
    from kubernetes_tpu.state.encode import Encoder

    t0 = time.perf_counter()
    nodes, existing, pending = small_cluster(seed, n_nodes, n_pods)
    enc = Encoder()
    enc.vocabs.label_keys.intern(UNSCHEDULABLE_TAINT_KEY)
    enc.vocabs.label_vals.intern("")
    tables, ex, pe, d = enc.encode_cluster(nodes, existing, pending, None)
    keys = (jnp.int32(enc.vocabs.label_keys.get(UNSCHEDULABLE_TAINT_KEY)),
            jnp.int32(enc.vocabs.label_vals.get("")))
    tables, ex, pe = (jax.device_put(t) for t in (tables, ex, pe))
    hw, ecfg = jnp.float32(1.0), default_engine_config()

    def run(engine, return_waves=False):
        return _schedule_batch_impl(tables, pe, keys, d.D, ex, engine, hw,
                                    ecfg, (), (), None, return_waves)

    mask_dev = _feasible(tables, pe, keys, d.D, ex)
    scan_res = run("scan")
    wave_res, wave_idx = run("waves", return_waves=True)
    on = platforms_of((mask_dev, scan_res.node, wave_res.node))
    mask = np.asarray(mask_dev)
    scan_node = np.asarray(scan_res.node)[:n_pods]
    wave_node = np.asarray(wave_res.node)[:n_pods]
    wave_idx = np.asarray(wave_idx)[:n_pods]
    t_device = time.perf_counter() - t0

    mismatches = []
    for pi, pod in enumerate(pending):
        for ni, node in enumerate(nodes):
            if bool(mask[pi, ni]) != oracle_fits(pod, node, nodes, existing):
                mismatches.append(f"filter {pod.name}@{node.name}")

    # ---- scan engine: placement for placement ---- #
    queue = sorted(range(n_pods), key=lambda i: (-pending[i].priority,
                                                 pending[i].creation_index))
    world = list(existing)
    exact_argmax = near_ties = 0
    for i in queue:
        pod, ni = pending[i], int(scan_node[i])
        feasible = [n for n in nodes if oracle_fits(pod, n, nodes, world)]
        if ni < 0:
            if feasible:
                mismatches.append(f"scan {pod.name}: unscheduled, oracle "
                                  f"fits {feasible[0].name}")
            continue
        chosen = nodes[ni]
        if chosen not in feasible:
            mismatches.append(f"scan {pod.name}@{chosen.name}: infeasible")
            continue
        scores = oracle_scores(pod, nodes, world)
        best = max(scores[n.name] for n in feasible)
        if best - scores[chosen.name] > 0.05:
            mismatches.append(
                f"scan {pod.name}@{chosen.name}: oracle score "
                f"{scores[chosen.name]:.3f} < best {best:.3f}")
        # the oracle's own pick: lowest-index node at the maximum
        first = next(n for n in feasible if scores[n.name] >= best - 1e-9)
        if first is chosen:
            exact_argmax += 1
        else:
            near_ties += 1
        world.append(dataclasses.replace(pod, node_name=chosen.name))

    # ---- waves engine: a valid greedy execution ---- #
    placed = sorted((int(wave_idx[i]), -pending[i].priority,
                     pending[i].creation_index, i)
                    for i in range(n_pods) if wave_node[i] >= 0)
    world = list(existing)
    for _w, _p, _c, i in placed:
        node = nodes[int(wave_node[i])]
        if not oracle_fits(pending[i], node, nodes, world):
            mismatches.append(f"waves {pending[i].name}@{node.name}: "
                              f"violates the oracle at replay")
        world.append(dataclasses.replace(pending[i], node_name=node.name))

    return {
        "ok": not mismatches and on <= {jax.devices()[0].platform},
        "nodes": n_nodes, "pods": n_pods, "seed": seed,
        "filter_pairs_checked": n_pods * n_nodes,
        "scan_scheduled": int((scan_node >= 0).sum()),
        "scan_argmax_exact": exact_argmax,
        "scan_argmax_within_tolerance": near_ties,
        "waves_scheduled": int((wave_node >= 0).sum()),
        "mismatches": mismatches[:20], "mismatch_count": len(mismatches),
        "result_platforms": sorted(on),
        "device_seconds": round(t_device, 3),
        "seconds": round(time.perf_counter() - t0, 3),
    }


# --------------------------------------------------------------------------- #
# full-size invariants: plain host code over what the apiserver holds
# --------------------------------------------------------------------------- #


def check_invariants(node_objs, pod_objs, check_spread: bool) -> list:
    """Violations among the BOUND pods the apiserver lists: no node over its
    allocatable in any resource or pod count; no required anti-affinity term
    with another matching pod in its topology domain; and (while nothing has
    been deleted — a deletion can widen a skew that was legal when each pod
    was placed) every DoNotSchedule spread constraint within maxSkew."""
    from kubernetes_tpu.api import semantics as sem
    from kubernetes_tpu.api.types import UnsatisfiableAction
    from kubernetes_tpu.api.v1 import node_from_v1, pod_from_v1

    nodes = {n.name: n for n in map(node_from_v1, node_objs)}
    bound = [p for p in map(pod_from_v1, pod_objs) if p.node_name]
    bad = []

    use: dict = {}
    for p in bound:
        if p.node_name not in nodes:
            bad.append(f"{p.key} bound to unknown node {p.node_name}")
            continue
        u = use.setdefault(p.node_name, {"pods": 0})
        u["pods"] += 1
        for k, v in (("cpu", p.requests.milli_cpu),
                     ("memory", p.requests.memory_kib),
                     ("ephemeral", p.requests.ephemeral_kib),
                     *p.requests.scalars):
            u[k] = u.get(k, 0) + v
    for name, u in use.items():
        a = nodes[name].allocatable
        cap = {"pods": a.pods, "cpu": a.milli_cpu, "memory": a.memory_kib,
               "ephemeral": a.ephemeral_kib, **dict(a.scalars)}
        for k, v in u.items():
            if v > cap.get(k, 0):
                bad.append(f"node {name}: {k} {v} > allocatable "
                           f"{cap.get(k, 0)}")

    def domain(p, key):
        return nodes[p.node_name].labels.get(key) \
            if p.node_name in nodes else None

    anti: dict = {}
    spread: dict = {}
    for p in bound:
        for t in p.affinity.anti_required:
            anti.setdefault((sem.term_namespaces(t, p), t.selector,
                             t.topology_key), []).append(p)
        for c in p.topology_spread:
            if c.when_unsatisfiable == UnsatisfiableAction.DO_NOT_SCHEDULE:
                spread.setdefault((p.namespace, c.selector, c.topology_key,
                                   c.max_skew, tuple(sorted(
                                       p.node_selector.items())),
                                   p.affinity.node_required), p)
    for (namespaces, sel, key), holders in anti.items():
        per_domain: dict = {}
        for q in bound:
            if q.namespace in namespaces and sem.selector_matches(
                    sel, q.labels):
                per_domain.setdefault(domain(q, key), set()).add(q.key)
        for h in holders:
            dom = domain(h, key)
            others = per_domain.get(dom, set()) - {h.key}
            if dom is not None and others:
                bad.append(f"anti-affinity: {h.key} shares {key}={dom} with "
                           f"{sorted(others)[0]} (+{len(others) - 1})")
    if check_spread:
        for (ns, sel, key, skew, _nsel, _naff), sample in spread.items():
            # domains that count: nodes carrying the key that the pod's own
            # node selector / required node affinity admits
            counts = {n.labels[key]: 0 for n in nodes.values()
                      if key in n.labels
                      and sem.pod_matches_node_selector(sample, n)}
            for q in bound:
                if q.namespace == ns and domain(q, key) in counts \
                        and sem.selector_matches(sel, q.labels):
                    counts[domain(q, key)] += 1
            if counts and max(counts.values()) - min(counts.values()) > skew:
                bad.append(f"spread: {sample.labels} over {key}: "
                           f"max {max(counts.values())} - min "
                           f"{min(counts.values())} > maxSkew {skew}")
    return bad


# --------------------------------------------------------------------------- #
# serving leg
# --------------------------------------------------------------------------- #


def pod_object(pod) -> dict:
    """api/v1.py pod_to_v1 plus what the apiserver's validation requires of a
    Pod it is asked to create (the wire form carries no image)."""
    from kubernetes_tpu.api.v1 import pod_to_v1

    obj = pod_to_v1(pod)
    obj.update(apiVersion="v1", kind="Pod")
    obj["spec"]["containers"][0]["image"] = "registry/app:v1"
    return obj


class BindWatch:
    """One watch stream on pods, the way a client observes binds: which node
    each pod was bound to, and whether any pod was ever seen on two."""

    def __init__(self, client):
        self.bound: dict = {}
        self.rebound: list = []
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self._watch = client.pods.watch("default")
        self._thread = threading.Thread(target=self._pump, daemon=True,
                                        name="chip-smoke-bind-watch")
        self._thread.start()

    def _pump(self):
        while not self._stop.is_set():
            ev = self._watch.next(timeout=1)
            if ev is None:
                continue
            obj = ev.object or {}
            name = obj.get("metadata", {}).get("name", "")
            node = (obj.get("spec") or {}).get("nodeName")
            if not node:
                continue
            with self._mu:
                prev = self.bound.setdefault(name, node)
                if prev != node:
                    self.rebound.append((name, prev, node))

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)


def wait_until(cond, timeout: float, interval: float = 0.05) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


class WaveLog:
    """The flight recorder's wave records, merged by sequence number on every
    poll so none is lost when the bounded ring wraps."""

    def __init__(self, server, unbound):
        self.server = server
        self.unbound = unbound   # () -> live pods the apiserver has not bound
        self.by_seq: dict = {}

    def poll(self) -> None:
        for r in self.server.scheduler.telemetry.recorder.records():
            if r["seq"] not in self.by_seq and (r.get("stats") or {}).get(
                    "attempted"):
                log(f"wave {r['seq']}: {r['stats']} "
                    f"snapshot={r.get('snapshot_mode')} "
                    f"{r['duration_s']:.2f}s "
                    f"{[(p, round(dt, 2)) for p, dt in r['phases'] if dt >= 0.05]}")
            self.by_seq[r["seq"]] = r

    def settle(self, timeout: float, quiet: float = 2.0) -> bool:
        """Wait until the scheduler has nothing left it could act on, for
        `quiet` seconds: the active and backoff lanes empty, and every pod
        that is still unbound parked in the unschedulable (or deferred) lane
        — none popped into a wave in flight, none still on its way in
        through the informer."""
        queue = self.server.scheduler.queue
        state = {"since": time.monotonic(), "logged": time.monotonic()}

        def idle():
            d = queue.depths()
            unbound = self.unbound()
            self.poll()
            now = time.monotonic()
            if now - state["logged"] >= 15:
                state["logged"] = now
                log(f"queue {d} unbound {unbound}")
            if d["active"] or d["backoff"] \
                    or unbound != d["unschedulable"] + d["deferred"]:
                state["since"] = now
                return False
            return now - state["since"] >= quiet

        return wait_until(idle, timeout, interval=0.2)

    def waves(self) -> list:
        """Every non-idle wave, compacted."""
        self.poll()
        out = []
        for _seq, r in sorted(self.by_seq.items()):
            st = r.get("stats") or {}
            if not st.get("attempted"):
                continue
            phases = {p: round(dt, 4) for p, dt in r.get("phases", ())}
            out.append({
                "t_start": round(r["t_start"], 3),
                "attempted": st["attempted"], "scheduled": st["scheduled"],
                "unschedulable": st["unschedulable"],
                "snapshot_mode": r.get("snapshot_mode"),
                "engine": r.get("engine"), "bucket": r.get("bucket"),
                "seconds": round(r["duration_s"], 4),
                "snapshot_seconds": phases.get("snapshot"),
                # dispatch -> blocking readback of the placements
                "dispatch_readback_seconds": round(
                    phases.get("dispatch", 0) + phases.get("readback", 0), 4),
                "device_split": r.get("device_split"),
                "bind_commit_seconds": phases.get("bind-commit"),
                "requeue_seconds": phases.get("requeue"),
                "supervisor_events": r.get("supervisor_events"),
            })
        return out


def serving_dims(n_nodes: int, n_pods: int, churn: int):
    """Capacities provisioned for the whole run, so no cycle after the first
    crosses a bucket and recompiles: room for the burst's extra node (one
    more hostname domain) and pod classes (the flagship has 50), and E taken
    through grown_for (the bound-pod axis doubles, state/dims.py)."""
    from kubernetes_tpu.state.dims import Dims, bucket

    return Dims(N=bucket(n_nodes + 1), D=bucket(n_nodes + 1),
                P=bucket(n_pods), SC=64, SL=64).grown_for(
                    E=n_pods + 2 * churn + 256)


def serving_leg(n_nodes: int, n_pods: int, churn: int, mesh=None,
                timeout: float = 900.0, quiet: float = 2.0) -> dict:
    import jax

    from kubernetes_tpu.api.v1 import node_to_v1
    from kubernetes_tpu.apiserver import APIServer
    from kubernetes_tpu.client import Client
    from kubernetes_tpu.models.workloads import flagship_pods, make_nodes
    from kubernetes_tpu.sched.cycle import _schedule_batch, snapshot_with_keys
    from kubernetes_tpu.sched.ledger import BindIntentLedger
    from kubernetes_tpu.sched.preemption import APIEvictor, Preemptor
    from kubernetes_tpu.sched.scheduler import Scheduler
    from kubernetes_tpu.sched.server import APIBinder, SchedulerServer
    from kubernetes_tpu.state.dims import bucket

    out: dict = {"nodes": n_nodes, "pods": n_pods, "churn": churn,
                 "mesh": mesh or 1}
    failures: list = []
    platform = jax.devices()[0].platform

    api = APIServer()
    client = Client.local(api)
    out["kvstore_backend"] = type(api.storage.kv).__name__
    if out["kvstore_backend"] != "NativeKV":
        failures.append(f"kvstore backend is {out['kvstore_backend']}, "
                        "not the native store")

    # ---- load the cluster through the client ---- #
    t0 = time.perf_counter()
    all_pods = flagship_pods(n_pods + 2 * churn)
    for n in make_nodes(n_nodes):
        client.nodes.create(node_to_v1(n))
    for p in all_pods[:n_pods]:
        client.pods.create(pod_object(p))
    # set-up times, kept apart from the steady cycles below
    setup = out["setup"] = {
        "load_seconds": round(time.perf_counter() - t0, 3)}
    log(f"serving: {n_nodes} nodes + {n_pods} pods created in "
        f"{setup['load_seconds']}s")

    # ---- the scheduler process: one cycle carries the whole backlog ---- #
    sched = Scheduler(
        binder=APIBinder(client), batch_size=bucket(n_pods), mesh=mesh,
        base_dims=serving_dims(n_nodes, n_pods, churn))
    # victims are evicted through the API, as SchedulerServer wires it
    sched.preemptor = Preemptor(evictor=APIEvictor(client))
    server = SchedulerServer(
        client, scheduler=sched, cycle_interval=0.02, batch_window=0.15,
        ledger=BindIntentLedger(api.storage, identity="chip-smoke"))
    watch = BindWatch(client)
    live = {p.name for p in all_pods[:n_pods]}   # created and not deleted
    wlog = WaveLog(server,
                   lambda: sum(1 for n in live if n not in watch.bound))
    try:
        t0 = time.perf_counter()
        server.start()   # informers list + sync: every node and pod ingested
        setup["ingest_seconds"] = round(time.perf_counter() - t0, 3)

        # ---- phase 1: the backlog ---- #
        ok = wlog.settle(timeout, quiet)
        out["backlog_seconds"] = round(time.perf_counter() - t0, 3)
        # where the backlog landed, before churn makes runs diverge on
        # timing: what a 4-chip run is compared with a 1-chip run on
        with watch._mu:
            out["backlog_placements"] = dict(watch.bound)
        out["backlog_bound"] = len(out["backlog_placements"])
        out["backlog_placements_sha256"] = hashlib.sha256(json.dumps(
            sorted(out["backlog_placements"].items())).encode()).hexdigest()
        log(f"serving: backlog settled={ok} bound={out['backlog_bound']} "
            f"in {out['backlog_seconds']}s")
        if not ok:
            failures.append("backlog did not settle: "
                            f"{sched.queue.depths()}")
        bad = check_invariants(client.nodes.list()["items"],
                               client.pods.list("default")["items"],
                               check_spread=True)
        out["backlog_violations"] = len(bad)
        failures += bad[:10]

        # ---- phase 2: churn on the incremental path, twice ---- #
        for rnd in range(2):
            done = [n for n in sorted(watch.bound)
                    if n.startswith("pod-") and n in live][:churn // 2]
            for name in done:   # completed pods leave
                client.pods.delete(name, "default")
            live.difference_update(done)
            node = client.nodes.get(f"node-{rnd}", "")
            node["metadata"].setdefault("labels", {})["smoke/round"] = \
                str(rnd)
            client.nodes.update(node, "")
            lo = n_pods + rnd * churn // 2
            fresh = all_pods[lo:lo + churn // 2]
            live.update(p.name for p in fresh)
            for p in fresh:
                client.pods.create(pod_object(p))
            if not wlog.settle(timeout / 3, quiet):
                failures.append(f"churn round {rnd} did not settle: "
                                f"{sched.queue.depths()}")
            log(f"serving: churn round {rnd}: -{len(done)} +{len(fresh)} "
                f"pods, {sum(p.name in watch.bound for p in fresh)} of the "
                "new bound")
        out["churn_bound"] = sum(
            1 for p in all_pods[n_pods:n_pods + churn]
            if p.name in watch.bound)

        # ---- phase 3: the preemption burst (bench.py control stage) ---- #
        vip = make_nodes(1)[0]
        vip = dataclasses.replace(
            vip, name="vip-node",
            labels={**vip.labels, HOSTNAME: "vip-node", "smoke/vip": "true"})
        client.nodes.create(node_to_v1(vip))

        def burst_pod(name, cpu, mem, **spec):
            return {"apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": name, "namespace": "default"},
                    "spec": {**spec, "containers": [{
                        "name": "c", "image": "i", "resources": {
                            "requests": {"cpu": cpu, "memory": mem}}}]}}

        for i in range(4):
            client.pods.create(burst_pod(
                f"filler-{i}", "7", "24Gi", nodeName="vip-node", priority=0))
        t0 = time.perf_counter()
        live.update(f"vip-{i}" for i in range(4))
        for i in range(4):
            client.pods.create(burst_pod(
                f"vip-{i}", "6", "20Gi", priority=1000,
                nodeSelector={"smoke/vip": "true"}))
        def vips_bound():
            return sum(watch.bound.get(f"vip-{i}") == "vip-node"
                       for i in range(4))

        burst_ok = wait_until(lambda: vips_bound() == 4, timeout / 3)
        out["preempt_burst_seconds"] = round(time.perf_counter() - t0, 3)
        out["preempt_bound"] = vips_bound()
        out["preempt_victims_evicted"] = len(sched.preemptor.evictor.evicted)
        if not burst_ok or not out["preempt_victims_evicted"]:
            failures.append(
                f"preemption burst: {out['preempt_bound']}/4 bound, "
                f"{out['preempt_victims_evicted']} victims evicted")
        wlog.settle(timeout / 3, quiet)

        # ---- what came out ---- #
        sched.prewarmer.wait(timeout)   # the next-bucket background compile
        waves = wlog.waves()
        out["waves"] = waves
        out["cycles"] = len(waves)
        out["patch_cycles"] = sum(1 for w in waves
                                  if w["snapshot_mode"] == "patch")
        if out["cycles"] < 3 or out["patch_cycles"] < 2:
            failures.append(f"{out['cycles']} cycles, {out['patch_cycles']} "
                            "on the patch path; need >= 3 and >= 2")
        first = waves[0] if waves else {}
        steady = [w for w in waves[1:] if w["attempted"] >= churn // 4]
        setup["cold_encode_seconds"] = first.get("snapshot_seconds")
        setup["first_cycle_compile_and_run_seconds"] = \
            first.get("dispatch_readback_seconds")
        out["steady_cycle_seconds"] = [w["seconds"] for w in steady]
        out["first_cycle"] = {k: first.get(k) for k in
                              ("attempted", "scheduled", "unschedulable")}

        pods_now = client.pods.list("default")["items"]
        bound_now = {p["metadata"]["name"]: p["spec"]["nodeName"]
                     for p in pods_now if p.get("spec", {}).get("nodeName")}
        out["scheduled"] = len(bound_now)
        out["unschedulable"] = len(pods_now) - len(bound_now)
        bad = check_invariants(client.nodes.list()["items"], pods_now,
                               check_spread=False)
        out["final_violations"] = len(bad)
        failures += bad[:10]
        # exactly one Binding per bound pod: the watch saw each land on the
        # node it is on now, and never on another
        with watch._mu:
            seen, rebound = dict(watch.bound), list(watch.rebound)
        stray = [n for n, node in bound_now.items() if seen.get(n) != node]
        out["double_bound"] = len(rebound)
        if rebound or stray:
            failures.append(f"bindings: {len(rebound)} pods seen on two "
                            f"nodes, {len(stray)} bound pods the watch did "
                            f"not see land ({stray[:3]})")
        out["intents_written"] = sched.ledger.intents_written
        out["intents_unretired"] = len(sched.ledger.unretired())
        if out["intents_unretired"]:
            failures.append(f"{out['intents_unretired']} intents unretired")

        # ---- nothing hid the device ---- #
        stats = sched.supervisor.stats
        out["supervisor"] = {k: getattr(stats, k) for k in SUPERVISOR_ZERO}
        out["supervisor"]["last_failure"] = stats.last_failure
        out["supervisor"]["healthy"] = sched.supervisor.healthy
        if any(getattr(stats, k) for k in SUPERVISOR_ZERO) \
                or not sched.supervisor.healthy:
            failures.append(f"supervisor: {out['supervisor']}")
        out["wave_errors"] = server.wave_errors
        if server.wave_errors:
            failures.append(f"{server.wave_errors} waves raised: "
                            f"{server.last_wave_error!r}")
        pw = sched.prewarmer
        out["prewarm"] = {
            "executables": len(pw.compiled), "hits": pw.hits,
            "type_error_drops": pw.type_error_drops,
            "compiled": [f"{d.N}x{d.P}x{d.E} {eng}"
                         for d, eng in pw.warm_log]}
        if pw.type_error_drops:
            failures.append(f"{pw.type_error_drops} prewarmed executables "
                            "dropped on TypeError")
        with server._mu:
            snap, keys = snapshot_with_keys(
                sched.cache, sched.encoder, [], sched.base_dims,
                mesh=sched.supervisor.snapshot_mesh())
            res = _schedule_batch(snap.tables, snap.pending, keys,
                                  snap.dims.D, snap.existing, dims=snap.dims)
            jax.block_until_ready(res.node)
        on = platforms_of((snap.tables, snap.existing, snap.pending,
                           res.node))
        out["array_platforms"] = sorted(on)
        if on != {platform}:
            failures.append(f"arrays on {sorted(on)}, not {platform}")
        out["dims"] = snap.dims
        leaf = snap.tables.nodes.alloc
        out["node_table_shards"] = sorted(
            {(s.device.id, tuple(s.data.shape))
             for s in leaf.addressable_shards})
        if mesh:
            if len({dev for dev, _ in out["node_table_shards"]}) != mesh:
                failures.append("node tables are not split over "
                                f"{mesh} devices: {out['node_table_shards']}")
            out["resident"] = {
                "full_uploads": sched.cache.resident_full_uploads,
                "donated_patches": sched.cache.resident_donated_patches,
                "copy_patches": sched.cache.resident_copy_patches,
                "donation_failures": sched.cache.resident_donation_failures}
            if sched.cache.resident_donation_failures \
                    or not sched.cache.resident_donated_patches:
                failures.append(f"mesh residency: {out['resident']}")
        out["placements"] = bound_now
    finally:
        watch.stop()
        server.stop()
        api.close()
    out["failures"] = failures
    out["ok"] = not failures
    return out


# --------------------------------------------------------------------------- #
# extender leg
# --------------------------------------------------------------------------- #


def extender_leg(n_nodes: int) -> dict:
    """Real HTTP filter + prioritize against the device lattice, answers
    checked against the oracle's predicate chain."""
    import jax

    from kubernetes_tpu.api.types import Pod, Resources
    from kubernetes_tpu.api.v1 import pod_to_v1
    from kubernetes_tpu.extender.backend import ExtenderBackend
    from kubernetes_tpu.extender.server import ExtenderServer
    from kubernetes_tpu.models.workloads import flagship_pods, make_nodes
    from kubernetes_tpu.state.dims import Dims, bucket

    nodes = make_nodes(n_nodes)
    names = [n.name for n in nodes]
    by_name = {n.name: n for n in nodes}
    backend = ExtenderBackend(base_dims=Dims(N=bucket(n_nodes)))
    backend.sync_nodes(nodes)
    # a bound population for the lattice to count: one flagship replica on
    # each of the first nodes
    bound = [dataclasses.replace(p, node_name=names[i])
             for i, p in enumerate(flagship_pods(min(100, n_nodes // 2)))]
    backend.sync_scheduled_pods(bound)
    # the oracle's spread/affinity predicates walk every node and bound pod
    # per (pod, node) pair: pods that carry them are held to it on the nodes
    # that hold the bound pods plus an even sample, the others on every node
    sample = nodes[:len(bound)] + nodes[len(bound)::max(n_nodes // 50, 1)]
    requests = [
        flagship_pods(51)[50],                              # spread only
        flagship_pods(2)[1],                                # + anti-affinity
        Pod(name="zonal", node_selector={ZONE: "zone-3"},
            requests=Resources.make(cpu="1", memory="1Gi")),
        Pod(name="giant", requests=Resources.make(cpu="64", memory="1Gi")),
    ]
    out: dict = {"nodes": n_nodes, "requests": []}
    failures: list = []

    def post(url, verb, body):
        req = urllib.request.Request(
            f"{url}/{verb}", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as r:
            return r.status, json.loads(r.read())

    with ExtenderServer(backend) as srv:
        for pod in requests:
            args = {"Pod": pod_to_v1(pod), "NodeNames": names}
            t0 = time.perf_counter()
            code, flt = post(srv.url, "filter", args)
            t_filter = time.perf_counter() - t0
            passing = flt.get("NodeNames") or []
            ok_set = set(passing)
            aff = pod.affinity
            held_to = sample if (pod.topology_spread or aff.pod_required
                                 or aff.anti_required) else nodes
            wrong = [n.name for n in held_to if (n.name in ok_set)
                     != oracle_fits(pod, n, nodes, bound, by_name)]
            if code != 200 or flt.get("Error") or wrong:
                failures.append(
                    f"filter {pod.name}: http {code} error "
                    f"{flt.get('Error')!r}, {len(wrong)} of {len(held_to)} "
                    f"nodes disagree with the oracle ({wrong[:3]})")
            if set(flt.get("FailedNodes") or {}) != set(names) - ok_set:
                failures.append(f"filter {pod.name}: FailedNodes does not "
                                "cover the rejected nodes")
            t0 = time.perf_counter()
            code, prio = post(srv.url, "prioritize",
                              {"Pod": pod_to_v1(pod), "NodeNames": passing})
            t_prio = time.perf_counter() - t0
            scores = {h["Host"]: h["Score"] for h in prio} \
                if isinstance(prio, list) else {}
            if code != 200 or set(scores) != set(passing) or any(
                    not 0 <= s <= 10 for s in scores.values()):
                failures.append(f"prioritize {pod.name}: http {code}, "
                                f"{len(scores)} scores for {len(passing)}")
            out["requests"].append({
                "pod": pod.name, "feasible": len(passing),
                "failed": len(flt.get("FailedNodes") or {}),
                "nodes_held_to_oracle": len(held_to),
                "filter_seconds": round(t_filter, 3),
                "prioritize_seconds": round(t_prio, 3)})
        out["http_requests_served"] = srv.requests_served
    snap, _ = backend._snapshot_for(requests[0])
    on = platforms_of(snap.tables)
    out["array_platforms"] = sorted(on)
    if on != {jax.devices()[0].platform}:
        failures.append(f"extender tables on {sorted(on)}")
    out["failures"] = failures
    out["ok"] = not failures
    return out


# --------------------------------------------------------------------------- #
# --programs: the builder's compile census
# --------------------------------------------------------------------------- #


def programs_census(n_nodes: int, n_pods: int) -> dict:
    """AOT-compile, through the prewarmer's own path, the programs the legs
    do not reach, at the flagship Dims: the `scan` engine, the explain
    tail, the fleet cycle at the bench `fleet` stage's shape, and the
    gang program at n_nodes x 2*n_pods — which is also RUN (the single
    device-loop program, ops/gang.py assign_gang). Per program: compiled or
    the compiler's refusal, compile seconds, memory_analysis() bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubernetes_tpu.models.workloads import (
        flagship_pods, gang_workload_pods, make_nodes)
    from kubernetes_tpu.sched import cycle
    from kubernetes_tpu.sched.prewarm import (
        BucketPrewarmer, abstract_cycle_args)
    from kubernetes_tpu.sched.supervisor import DispatchSupervisor
    from kubernetes_tpu.state.dims import Dims, bucket
    from kubernetes_tpu.state.encode import Encoder

    def encode(pods, base):
        enc = Encoder()
        enc.vocabs.label_keys.intern(cycle.UNSCHEDULABLE_TAINT_KEY)
        enc.vocabs.label_vals.intern("")
        tables, ex, pe, d = enc.encode_cluster(
            make_nodes(n_nodes), [], pods, base)
        keys = (jnp.int32(enc.vocabs.label_keys.get(
            cycle.UNSCHEDULABLE_TAINT_KEY)),
            jnp.int32(enc.vocabs.label_vals.get("")))
        return enc, tables, ex, pe, d, keys

    def mem(compiled):
        m = compiled.memory_analysis()
        return {k: getattr(m, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(m, k)}

    programs = []

    def census(name, d, compile_fn):
        log(f"programs: compiling {name} at N={d.N} P={d.P} E={d.E}")
        t0 = time.perf_counter()
        rec = {"program": name, "dims": f"N={d.N} P={d.P} E={d.E}"}
        try:
            compiled = compile_fn()
            rec.update(compiled=True, memory=mem(compiled))
        except Exception as e:  # noqa: BLE001 - the refusal IS the record
            rec.update(compiled=False, refusal=repr(e)[:600])
        rec["compile_seconds"] = round(time.perf_counter() - t0, 2)
        log(f"programs: {rec}")
        programs.append(rec)
        return rec

    def via_prewarmer(d, engine, gang=False, fleet=None):
        """sched/prewarm.py _compile stores the executable or reports the
        failure to its supervisor; surface whichever happened."""
        pw = BucketPrewarmer()
        pw.supervisor = DispatchSupervisor(prewarmer=pw)
        pw._compile(d, engine, (), gang, None, fleet)
        compiled = pw.lookup(d, engine, (), gang, fleet=fleet)
        if compiled is None:
            raise RuntimeError(pw.supervisor.stats.last_failure)
        return compiled

    _enc, _t, _ex, _pe, d, _k = encode(
        flagship_pods(n_pods), serving_dims(n_nodes, n_pods, CHURN))
    census("scan", d, lambda: via_prewarmer(d, "scan"))

    def explain_tail():
        (tables, pending, keys, existing, hw, ecfg,
         _gang) = abstract_cycle_args(d)
        return cycle._schedule_batch_impl.lower(
            tables, pending, keys, d.D, existing, "waves", hw, ecfg, (), (),
            None, False, True).compile()

    census("waves + explain tail", d, explain_tail)

    # the bench `fleet` stage: 16 tenants x 1,000 nodes x 2,000 pods
    fd = Dims(N=bucket(1000), P=bucket(1000), E=bucket(2000 + 256))
    census("fleet cycle K=16", fd,
           lambda: via_prewarmer(fd, "waves", fleet=16))

    # ---- the gang program, compiled and run ---- #
    gang_pods = gang_workload_pods(2 * n_pods)
    enc, tables, ex, pe, gd, keys = encode(
        gang_pods, Dims(N=bucket(n_nodes), P=bucket(2 * n_pods)))
    gang = enc.build_gang_arrays(gang_pods, gd)
    census("gang device loop", gd,
           lambda: via_prewarmer(gd, "waves", gang=True))
    tables, ex, pe, gang = (jax.device_put(t) for t in (tables, ex, pe, gang))

    gang_run: dict = {"pods": 2 * n_pods, "groups": int(gd.GR)}
    try:
        times = []
        for _ in range(2):   # first call compiles
            t0 = time.perf_counter()
            res = cycle._schedule_batch(tables, pe, keys, gd.D, ex,
                                        gang=gang)
            node = np.asarray(jax.device_get(res.node))
            times.append(round(time.perf_counter() - t0, 3))
        gang_run["device_loop_seconds"] = times
        gang_run["scheduled"] = int((node >= 0).sum())
    except Exception as e:  # noqa: BLE001 - record what the runtime said
        gang_run["error"] = repr(e)[:600]
    log(f"programs: gang {gang_run}")

    # ---- numerics of the wave commit's matmuls (ops/waves.py) ---- #
    rng = np.random.default_rng(0)
    W = np.arange(1, 513, dtype=np.float32).reshape(8, 64)   # weight sums
    A = (rng.random((64, 256)) < 0.3)
    R = rng.integers(0, 2**24, (64, 4)).astype(np.int32)     # KiB-sized
    want_f = W.astype(np.float64) @ A
    want_i = A.T.astype(np.int64) @ R
    Aj = jnp.asarray(A)
    got_default = np.asarray(jnp.asarray(W) @ Aj.astype(jnp.float32))
    got_highest = np.asarray(jnp.matmul(
        jnp.asarray(W), Aj.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    got_int = np.asarray(jnp.einsum("cn,cr->nr", Aj.astype(jnp.int32),
                                    jnp.asarray(R)))
    numerics = {
        "f32_matmul_default_inexact_entries": int((got_default
                                                   != want_f).sum()),
        "f32_matmul_highest_inexact_entries": int((got_highest
                                                   != want_f).sum()),
        "int32_einsum_inexact_entries": int(
            (got_int.astype(np.int64) != want_i).sum()),
        "entries": int(want_f.size)}
    log(f"programs: numerics {numerics}")
    return {"ok": all(p["compiled"] for p in programs)
            and "error" not in gang_run
            and not numerics["f32_matmul_highest_inexact_entries"]
            and not numerics["int32_einsum_inexact_entries"],
            "programs": programs, "gang_run": gang_run,
            "numerics": numerics}


# --------------------------------------------------------------------------- #


class Deadline(BaseException):
    """The run's own time limit fired (SIGALRM), or someone sent SIGTERM:
    unwind through every `finally` — servers stopped, chip released by a
    normal interpreter exit — instead of dying with the chip in hand."""


def _on_alarm(signum, frame):  # noqa: ARG001 - signal signature
    raise Deadline()


def _jsonable(o):
    if dataclasses.is_dataclass(o) and not isinstance(o, type):
        return dataclasses.asdict(o)
    return str(o)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the serving leg's node axis over this many "
                         "chips")
    ap.add_argument("--programs", action="store_true",
                    help="run the builder's compile census instead of the "
                         "legs")
    ap.add_argument("--deadline", type=int, default=1150,
                    help="seconds after which the run fails itself (the "
                         "contract allows 1200)")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    device = require_tpu()
    signal.signal(signal.SIGALRM, _on_alarm)
    signal.signal(signal.SIGTERM, _on_alarm)   # a kill from outside, too
    signal.alarm(args.deadline)
    if args.mesh > device["count"]:
        raise SystemExit(f"chip_smoke: --mesh {args.mesh} on "
                         f"{device['count']} devices")
    from kubernetes_tpu.utils.platform import enable_compile_cache

    report: dict = {
        "device": device,
        "sizes": {"nodes": N_NODES, "pods": N_PODS, "churn": CHURN,
                  "oracle": "64x256"},
        # scale cut to fit the time limit (never the node count or the
        # constraint set); empty = the full BASELINE.json config 4
        "reduced": [],
        "compile_cache_dir": enable_compile_cache(),
    }
    serving = lambda: serving_leg(N_NODES, N_PODS, CHURN,  # noqa: E731
                                  mesh=args.mesh or None)
    if args.programs:
        legs = {"programs": lambda: programs_census(N_NODES, N_PODS)}
    elif args.mesh:
        legs = {"serving": serving}   # the sharded path; the rest is 1-chip
    else:
        legs = {"oracle": oracle_leg, "serving": serving,
                "extender": lambda: extender_leg(N_NODES)}
    ok = True
    for name, leg in legs.items():
        log(f"{name} leg")
        t0 = time.perf_counter()
        try:
            report[name] = leg()
        except Deadline:
            report[name] = {"ok": False, "error": "not finished when the "
                            f"{args.deadline} s deadline (or SIGTERM) came"}
            log(f"{name} leg: {report[name]['error']}")
            ok = False
            break
        except Exception as e:  # noqa: BLE001 - a phase error fails the run
            import traceback

            traceback.print_exc()
            report[name] = {"ok": False, "error": repr(e)[:600]}
        report[name]["leg_seconds"] = round(time.perf_counter() - t0, 2)
        ok = ok and bool(report[name].get("ok"))
        log(f"{name} leg ok={report[name].get('ok')} "
            f"in {report[name]['leg_seconds']}s")
    signal.alarm(0)
    report["memory"] = memory_stats()
    report["total_seconds"] = round(time.perf_counter() - t_start, 2)
    report["ok"] = ok

    os.makedirs(OUT_DIR, exist_ok=True)
    tag = "programs" if args.programs else f"mesh{args.mesh}" \
        if args.mesh else "1chip"
    with open(os.path.join(OUT_DIR, f"chip_smoke_{tag}.json"), "w") as f:
        json.dump(report, f, indent=1, default=_jsonable)
        f.write("\n")
    # the report line: the full report minus the bulky parts
    line = dict(report)
    if "serving" in line:
        line["serving"] = {k: v for k, v in line["serving"].items()
                           if k not in ("placements", "backlog_placements",
                                        "waves", "dims")}
    print(json.dumps(line, default=_jsonable), flush=True)
    # the result, last: exactly these keys, the device as JAX reported it
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
