"""Percentiles and spreads, the one arithmetic for every metric."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default): index ``q/100 * (n-1)``. Flooring
    the index, as ``bench_serve_llm._pct`` does, reports a lower rank
    than asked."""
    data = sorted(values)
    if not data:
        raise ValueError("percentile of no values")
    rank = q / 100.0 * (len(data) - 1)
    low = math.floor(rank)
    high = min(low + 1, len(data) - 1)
    return data[low] + (data[high] - data[low]) * (rank - low)


def median(values) -> float:
    return percentile(values, 50.0)


def spread(values) -> float:
    """Distance between the quartiles over the median: what the driver
    compares a bound with."""
    mid = median(values)
    return (percentile(values, 75.0) - percentile(values, 25.0)) / mid


def summary(values) -> dict:
    """What goes on the earlier lines beside a tail: count, p50, p90,
    p95, p99, max."""
    if not values:
        return {"n": 0}
    out = {"n": len(values)}
    for q in (50, 90, 95, 99):
        out[f"p{q}"] = percentile(values, q)
    out["max"] = max(values)
    return out
