"""One run of one cell: set-up, the measured window, the checks, the result.

Everything that belongs to one configuration, one traffic mix or one metric
is data (configs/, traffic/, metrics/) or a reader found by name (sources/);
this module holds only what every cell shares.
"""

from __future__ import annotations

import gc
import importlib
import json
import math
import os
import shutil
import signal
import time

from . import objects, reference, stats, traffic as traffic_mod
from . import trace as trace_mod
from .cluster import (BindWatch, Cluster, CompileCounter, WaveLog,
                      enable_compile_cache, log, settled, wait_until)

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
#: a run fails itself cleanly before the driver's limit (the first run of a
#: cell in a checkout may take 1200 s) rather than die with the chip in hand
DEADLINE_S = 1150


class Deadline(BaseException):
    """SIGALRM or SIGTERM: unwind through every `finally`."""


def _on_signal(signum, frame):  # noqa: ARG001 - signal signature
    raise Deadline()


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> tuple:
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; it "
                         f"has {[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, load_json(ROOT, conf["file"]), load_json(
        BENCH_DIR, "traffic", cell["traffic"] + ".json")


def metrics_of(bench: dict, section: str, workload: str) -> list:
    """The metrics of `section` this cell reports: those that list it under
    `workloads`, and those that list nothing."""
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def find_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it. Without `rehearse`, anything but a TPU
    with the chips the cell asks for ends the run with no result."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device}")
    if not rehearse and (device["platform"] != "tpu"
                         or device["count"] < chips):
        raise SystemExit(f"benchmark: the cell needs {chips} TPU chip(s); "
                         f"JAX found {device} (a rehearsal on the CPU takes "
                         "--rehearse and is never quoted)")
    return device


def memory_peak() -> dict:
    import jax

    peak = 0
    for d in jax.devices():
        peak = max(peak, int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)))
    return {"peak_bytes_in_use": peak}


def compute_metrics(bench: dict, section: str, workload: str,
                    obs: dict) -> dict:
    """Each metric of the section through its own file: metrics/<name>.json
    names a source reader (sources/<kind>.py), a reducer and a scale. A
    reader that finds nothing returns nothing and the metric is left out."""
    out = {}
    ctx = {"bound_in_window": obs["bound_in_window"],
           "window_s": obs["window_s"]}
    for m in metrics_of(bench, section, workload):
        spec = load_json(BENCH_DIR, "metrics", m["name"] + ".json")
        reader = importlib.import_module(
            f"benchmarks.harness.sources.{spec['source']['kind']}")
        value = stats.reduce(spec["reduce"], reader.read(obs, spec["source"]),
                             ctx)
        if value is None:
            continue
        out[m["name"]] = {"value": value * spec.get("scale", 1.0),
                          "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------- #
# set-up
# --------------------------------------------------------------------------- #


def create_all(resource, objs: list) -> float:
    t0 = time.perf_counter()
    for o in objs:
        resource.create(o)
    return time.perf_counter() - t0


def warm_up(cluster: Cluster, server, watch: BindWatch, groups, tr: dict,
            seed: int) -> None:
    """Drive the cell's own shapes once, on throw-away pods that are deleted
    again: `warmup_rounds` waves at the cell's capacities (the first compiles
    or loads the cycle, and in a configuration with priorities its
    unschedulable tail drives the preemption pass), then the patch-scatter
    ladder, then whatever compile-ahead the waves set off."""
    sched = server.scheduler
    for rnd in range(tr["warmup_rounds"]):
        warm = objects.pending_pods(
            groups, groups.n * tr["warmup_pods_per_group"], seed,
            f"warm{rnd}")
        names = [p["metadata"]["name"] for p in warm]
        base = sched.cache.pod_count
        create_all(cluster.client.pods, warm)
        if not wait_until(lambda: watch.count_bound(names) == len(names),
                          timeout=1000, interval=0.1):
            raise SystemExit(
                f"warm-up: {watch.count_bound(names)} of {len(names)} "
                f"throw-away pods bound; queue {sched.queue.depths()}, "
                f"wave errors {server.wave_errors} "
                f"({server.last_wave_error!r})")
        log(f"warm-up: round {rnd}: {len(names)} throw-away pods bound")
        for n in names:
            cluster.client.pods.delete(n, "default")
        if not wait_until(lambda: sched.cache.pod_count <= base
                          and settled(server, lambda: 0), timeout=60):
            raise SystemExit("warm-up: the throw-away pods did not leave "
                             f"the scheduler's cache ({sched.cache.pod_count}"
                             f" pods, {base} before)")
    sched.prewarmer.wait(900)
    log("warm-up: compile-ahead finished")
    rungs = cluster.warm_patch_ladder(server)
    log(f"warm-up: {tr['warmup_rounds']} round(s), {rungs} patch rungs "
        f"compiled, prewarmed {[(d.N, d.P, d.E, e) for d, e in sched.prewarmer.warm_log]}")


# --------------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------------- #


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, t_process: float = None,
             sabotage=None) -> tuple:
    """Returns (exit code, result dict). `sabotage(cluster, server)` is for
    the benchmark's own tests: it breaks the timed path underneath, and the
    run must then come out not correct."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, cfg, tr = find_cell(bench, workload)
    if rehearse:
        cfg = {**cfg, **cfg["rehearse"]}
    device = find_device(cell["chips"], rehearse)
    signal.signal(signal.SIGALRM, _on_signal)
    signal.signal(signal.SIGTERM, _on_signal)
    signal.alarm(DEADLINE_S)
    cache_dir = enable_compile_cache(ROOT)
    compiles = CompileCounter()
    log(f"cell {workload} seed {seed} seconds {seconds} trace {int(trace)} "
        f"cache {cache_dir}")

    cluster = Cluster(cfg)
    watch = BindWatch(cluster.client)
    try:
        code, result = _run(bench, cell, cfg, tr, cluster, watch, compiles,
                            device, seed, seconds, trace, rehearse,
                            t_process, sabotage)
    finally:
        signal.alarm(0)
        watch.stop()
        cluster.close()
    return code, result


def _run(bench, cell, cfg, tr, cluster, watch, compiles, device, seed,
         seconds, trace, rehearse, t_process, sabotage):
    import jax

    workload, kind = cell["name"], tr["kind"]
    client = cluster.client
    backlog = kind == "backlog"
    population = 0 if backlog else cfg["existing_pods"]
    work = cfg["backlog_pods"] if backlog else population
    groups = objects.Groups(cfg, seed, work // cfg["groups"])
    nodes = objects.make_nodes(cfg)
    prebound = objects.prebound_pods(groups, cfg["nodes"], population)
    bad = reference.final_state(nodes, prebound, check_spread=True)
    if bad:
        raise SystemExit(f"set-up: the pre-bound population breaks its own "
                         f"constraints: {bad[:3]}")

    # ---- load the cluster through the client ---- #
    t_load = create_all(client.nodes, nodes) + create_all(client.pods,
                                                          prebound)
    log(f"set-up: {len(nodes)} nodes + {len(prebound)} bound pods created "
        f"in {t_load:.1f}s")
    server = cluster.new_server()
    server.start()
    # the informers' sync returns when the list is delivered; the handlers
    # may still be feeding the scheduler's cache
    if not wait_until(lambda: server.scheduler.cache.pod_count
                      >= len(prebound), timeout=120):
        raise SystemExit("set-up: the scheduler ingested "
                         f"{server.scheduler.cache.pod_count} of "
                         f"{len(prebound)} bound pods")
    log("set-up: scheduler started, population ingested")
    warm_up(cluster, server, watch, groups, tr, seed)

    series: dict = {}
    gen = None
    if backlog:
        # the measured scheduler is a new process's worth of state over the
        # warm executables: a restart or failover with work waiting
        server.stop()
        pods = objects.pending_pods(groups, cfg["backlog_pods"], seed, "job")
        t_load = create_all(client.pods, pods)
        log(f"set-up: backlog of {len(pods)} pods created in {t_load:.1f}s")
        warm, server = server, cluster.new_server()
        cluster.adopt_warmth(warm, server)
    else:
        plan = traffic_mod.arrival_plan(tr, cfg, seconds, groups.n)
        pods = objects.pending_pods(groups, plan["creates"], seed, "job")
    if sabotage:
        sabotage(cluster, server)   # the measured scheduler, never the warm-up
    names = [p["metadata"]["name"] for p in pods]
    by_name = dict(zip(names, pods))
    wlog = WaveLog(server.scheduler)
    relists0 = cluster.counters(server)["info"]["informer_relists"]
    # everything the window sends exists; keep the collector off the millions
    # of long-lived objects set-up made (no gc.disable(): young garbage is
    # still collected)
    gc.collect()
    gc.freeze()
    log("set-up: done")

    # ---- the window ---- #
    trace_dir = os.path.join(ROOT, ".cache", "bench-trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # device and runtime events only
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_process
    compiles.arm()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_mod.MARK_OPEN):
        t_open = time.perf_counter()
    limit = t0 + seconds
    if backlog:
        server.start()   # informers list + sync: every node and pod ingested
        series["ingest_s"] = time.perf_counter() - t0
    else:
        gen = traffic_mod.Generator(client, watch, pods, plan, t0, limit)
        gen.start()
    while time.perf_counter() < limit:
        wlog.poll()
        if backlog and watch.count_bound(names) == len(names):
            break   # the fixed work is done: the drain's end is the result
        time.sleep(0.2)
    window_end = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_mod.MARK_CLOSE):
        t_close = time.perf_counter()
    compiles.disarm()
    seen = watch.snapshot()
    if trace:
        jax.profiler.stop_trace()
    if gen is not None and not gen.join(30):
        raise SystemExit("the generator did not finish its schedule")

    # ---- after the window: let the rest land, then look ---- #
    wait_until(lambda: watch.count_bound(names) == len(names),
               timeout=tr["settle_s"], interval=0.1)
    wait_until(lambda: settled(
        server, lambda: len(names) - watch.count_bound(names)), timeout=10)
    waves = wlog.waves(t0, window_end)
    final = watch.snapshot()
    counters = cluster.counters(server)
    memory = memory_peak()
    platforms = cluster.array_platforms(server)

    in_window = {n: t for n, t in seen["t_bound"].items() if n in by_name
                 and t <= window_end}
    window_s = window_end - t0
    if backlog:
        times = [final["t_bound"].get(n, math.inf) for n in names]
        series["drain_pods_per_s"] = stats.drain_rate(times, t0, window_end)
        series["first_bind_s"] = min(times) - t0 if in_window else None
        seen_at = sorted(t - t0 for t in times if not math.isinf(t))
        series["bindings_seen_at_s"] = {
            f"{q}%": round(seen_at[max(len(seen_at) * q // 100 - 1, 0)], 3)
            for q in (1, 25, 50, 75, 90, 99, 100)} if seen_at else {}
        attempted, failed = len(names), len(names) - len(in_window)
    else:
        due = {names[i]: t0 + d for i, d in enumerate(plan["create_due"])}
        lat, failed = stats.bind_latencies_ms(due, seen["t_bound"],
                                              window_end)
        series["bind_latency_ms"] = lat
        series["create_call_ms"] = gen.create_call_ms
        series["generator_late_ms"] = gen.late_ms
        series["achieved_rate_pct"] = 100.0 * gen.sent_creates / len(names)
        attempted = len(names)
        series["late_by_phase"] = _late_by_phase(gen, plan, t0, waves)
        log(f"generator: rate {plan['rate']:.1f}/s, {gen.sent_creates} "
            f"creates, {gen.deletes} deletes ({gen.deletes_skipped} skipped)"
            f", {len(gen.errors)} refused {gen.errors[:2]}; late ms p50/p95/"
            f"max {_pcts(gen.late_ms)}")
    series["setup_s"] = setup_s

    # ---- correct: the reference over the final state and the history ---- #
    listing = client.pods.list("default")["items"]
    bound_now = {p["metadata"]["name"]: p["spec"]["nodeName"]
                 for p in listing if p.get("spec", {}).get("nodeName")}
    violations = reference.final_state(
        client.nodes.list()["items"], listing, check_spread=backlog)
    replayed, infeasible = reference.replay(
        nodes, prebound, final["history"], by_name, pods[:1] + [
            groups.pod(g, f"shape-{g}") for g in range(groups.n)])
    stray = [n for n, node in bound_now.items()
             if final["bound"].get(n) != node]
    lost = [n for n in names if n not in final["bound"]]
    checks = {   # name: (value, limit); every comparison is exact
        "invariant_violations": len(violations),
        "bindings_infeasible_at_their_turn": len(infeasible),
        "pods_seen_on_two_nodes": len(final["rebound"]),
        "bound_pods_the_watch_saw_elsewhere": len(stray),
        "pods_never_bound": len(lost),
        "compilations_in_window": len(compiles.events),
        "generator_refusals": len(gen.errors) if gen else 0,
        "arrays_off_the_device": 0 if platforms == [device["platform"]]
        else 1,
        "store_not_native": 0 if cluster.kvstore == "NativeKV" else 1,
        **counters["zero"],
    }
    for name, value in checks.items():
        print(f"check {name}: {value} (limit 0) "
              f"{'ok' if value == 0 else 'FAILED'}", flush=True)
    print(f"check bindings_replayed: {replayed} of {len(names)} sent",
          flush=True)
    for what, items in (("violation", violations), ("infeasible", infeasible),
                        ("stray", stray), ("lost", lost),
                        ("compiled", compiles.events)):
        for item in items[:5]:
            log(f"{what}: {item}")
    correct = all(v == 0 for v in checks.values())
    failed += len(stray) + len(final["rebound"])

    # ---- the result ---- #
    obs = {"waves": waves, "series": series, "memory": memory,
           "bound_in_window": len(in_window), "window_s": window_s,
           "rehearse": rehearse, "device": device,
           "dims": {f: getattr(cluster.dims, f)
                    for f in ("N", "P", "E", "R", "L", "K", "SC")},
           "trace": None}
    dev_out = {**device, "memory_peak_bytes": memory["peak_bytes_in_use"]}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        neutral, held = trace_mod.load_xplane(trace_dir)
        log(f"trace held: {held}")
        red = trace_mod.reduce_trace(neutral, t_open, t_close, waves,
                                     rehearse)
        obs["trace"] = red
        dev_out.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    section = "per_layer" if trace else "end_to_end"
    result["metrics"] = compute_metrics(bench, section, workload, obs)
    result["device"] = dev_out
    # earlier lines: what the metrics were reduced from
    print("info " + json.dumps({
        "workload": workload, "seed": seed, "window_s": window_s,
        "setup_s": setup_s, "bound_in_window": len(in_window),
        "waves": [{"t": round(w["t_start"] - t0, 3), **w["stats"],
                   "s": w["duration_s"], "mode": w.get("snapshot_mode"),
                   "split": w.get("device_split"),
                   "phases": [(p, round(d, 4)) for p, d in w["phases"]
                              if d >= 0.02]} for w in waves][:6],
        "n_waves": len(waves),
        "informer_relists_in_window":
            counters["info"]["informer_relists"] - relists0,
        "watch_restarts": watch.restarts, **counters["info"],
        "latency_ms_p50_p95_max": _pcts(series.get("bind_latency_ms")),
        "generator_late_ms_p50_p95_max": _pcts(
            series.get("generator_late_ms")),
        "creates_over_10ms_late_by_scheduler_phase":
            series.get("late_by_phase"),
        "bindings_seen_at_s": series.get("bindings_seen_at_s"),
        "placements_sha256": _digest(final["bound"], by_name),
        "dims": obs["dims"]}, default=str), flush=True)
    return 0, result


def _late_by_phase(gen, plan: dict, t0: float, waves: list) -> dict:
    """Where the generator's lateness comes from: for every create sent more
    than 10 ms late, the flight-recorder phase the scheduler thread was in
    at the instant the create was due."""
    spans = []
    for w in waves:
        at = w["t_start"]
        for name, dt in w["phases"]:
            spans.append((at, at + dt, name))
            at += dt
    out: dict = {}
    for due, late in zip(plan["create_due"], gen.late_ms):
        if late <= 10.0:
            continue
        t = t0 + due
        name = next((n for a, b, n in spans if a <= t < b), "between-waves")
        out[name] = out.get(name, 0) + 1
    return out


def _pcts(samples) -> list:
    if not samples:
        return []
    return [round(stats.percentile(samples, q), 3) for q in (50, 95, 100)]


def _digest(bound: dict, by_name: dict) -> str:
    import hashlib

    return hashlib.sha256(json.dumps(sorted(
        (n, node) for n, node in bound.items() if n in by_name)).encode()
    ).hexdigest()[:16]
