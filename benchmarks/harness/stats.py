"""Metric arithmetic: the fixed vocabulary of reducers a metric file may
name, and the end-to-end definitions (latency from the due instant, unbound
pods as misses, the drain rate when the drain does not finish)."""

from __future__ import annotations

import math
import statistics


def percentile(samples: list, q: float) -> float:
    """The q-th percentile (0 < q <= 100) by nearest rank over ALL samples;
    `inf` samples (requests that never completed) sort last, so they miss any
    limit."""
    if not samples:
        raise ValueError("no samples")
    xs = sorted(samples)
    rank = max(math.ceil(q / 100.0 * len(xs)), 1)
    return xs[rank - 1]


def bind_latencies_ms(due: dict, seen: dict, window_end: float) -> tuple:
    """One sample per pod whose create was due in the window: the instant the
    client's watch saw its Binding minus the instant its create was DUE (a
    late generator or a stalled create is charged to the system). A pod not
    bound when the window closes gives the time it had waited by then, a
    floor on its latency, and is counted: returns (samples, unbound)."""
    out, unbound = [], 0
    for name, t_due in due.items():
        t = seen.get(name)
        if t is None or t > window_end:
            unbound += 1
            t = window_end
        out.append((t - t_due) * 1000.0)
    return out, unbound


def drain_rate(bind_times: list, t_start: float, window_end: float) -> float:
    """Bindings seen inside the window over the seconds from the start to the
    last of them, or to the end of the window when the backlog has not
    drained by then (`bind_times` then holds later ones too)."""
    inside = [t for t in bind_times if t <= window_end]
    if not inside:
        return 0.0
    drained = len(inside) == len(bind_times)
    end = max(inside) if drained else window_end
    return len(inside) / max(end - t_start, 1e-9)


def spread(values: list) -> float:
    """Distance between the first and third quartile as a share of the
    median: the contract's measure of how widely runs spread."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def reduce(how: str, samples, ctx: dict):
    """Apply reducer `how` to what a source returned. A source returns a list
    of numbers (one per wave, per call, per pod) or a single number."""
    if samples is None:
        return None
    if isinstance(samples, (int, float)):
        samples = [samples]
    samples = list(samples)
    if not samples:
        return None
    if how == "sum":
        return float(sum(samples))
    if how == "first":
        return float(samples[0])
    if how == "max":
        return float(max(samples))
    if how in ("p50", "p95", "p99"):
        return float(percentile(samples, float(how[1:])))
    if how == "per_bound_pod":
        return float(sum(samples)) / ctx["bound_in_window"] \
            if ctx.get("bound_in_window") else None
    if how == "rate":
        return len(samples) / ctx["window_s"]
    raise ValueError(f"unknown reducer {how!r}")
