"""The metric arithmetic on synthetic samples."""

import math

import pytest

from benchmarks.harness import stats


def test_percentiles_by_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 99) == 99
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latency_is_from_the_due_instant_and_unbound_pods_count():
    due = {"a": 10.0, "b": 10.5, "c": 11.0, "d": 11.5}
    # a bound 0.8 s after it was due; b's create was sent late, which is not
    # b's excuse; c bound after the window closed; d never
    seen = {"a": 10.8, "b": 12.0, "c": 20.5}
    lat, unbound = stats.bind_latencies_ms(due, seen, window_end=20.0)
    assert lat == pytest.approx([800.0, 1500.0, 9000.0, 8500.0])
    assert unbound == 2
    # an unbound pod's floor pulls the tail up: it misses any limit
    assert stats.percentile(lat, 95) == pytest.approx(9000.0)


def test_drain_rate_to_the_last_binding_or_the_window_end():
    times = [1.0 + 0.01 * i for i in range(1000)]   # last at 10.99
    assert stats.drain_rate(times, 1.0, 40.0) == pytest.approx(
        1000 / 9.99)
    # the drain has not finished when the window closes: the rate is over
    # the whole window, and only what landed inside counts
    late = times + [50.0, math.inf]
    assert stats.drain_rate(late, 1.0, 41.0) == pytest.approx(1000 / 40.0)
    assert stats.drain_rate([math.inf], 0.0, 10.0) == 0.0


def test_reducers():
    ctx = {"bound_in_window": 200, "window_s": 10.0}
    assert stats.reduce("sum", [1, 2, 3], ctx) == 6.0
    assert stats.reduce("first", [4, 5], ctx) == 4.0
    assert stats.reduce("max", [4, 5], ctx) == 5.0
    assert stats.reduce("p50", [1, 2, 3, 4], ctx) == 2.0
    assert stats.reduce("per_bound_pod", [0.1, 0.3], ctx) == pytest.approx(
        0.002)
    assert stats.reduce("rate", [0.5] * 20, ctx) == 2.0
    assert stats.reduce("first", 3.5, ctx) == 3.5
    assert stats.reduce("sum", [], ctx) is None      # nothing to read
    assert stats.reduce("p95", None, ctx) is None
    with pytest.raises(ValueError):
        stats.reduce("mean", [1], ctx)


def test_spread_is_the_contracts():
    vals = [100, 101, 102, 103, 104, 105]
    import statistics
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q[2] - q[0]) / 102.5)
