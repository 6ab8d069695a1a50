"""Percentile, window and spread arithmetic (stdlib only)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) with linear interpolation between
    order statistics (numpy's default). Raises on no values: a metric of
    nothing is left out, not reported as 0."""
    data = sorted(values)
    if not data:
        raise ValueError("percentile of no values")
    pos = (len(data) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return data[lo] + (data[hi] - data[lo]) * (pos - lo)


def spread(values) -> float:
    """Distance between the first and third quartile
    (``statistics.quantiles(values, n=4)``) as a share of the median: the
    spread the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def window_span(records) -> float:
    """Seconds from the window's first POST to its last completion."""
    return max(r["done"] for r in records) - min(r["posted"] for r in records)


def merged_intervals(intervals) -> list[tuple[float, float]]:
    """``(start, end)`` intervals with every overlap merged, in order."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def union_seconds(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    return sum(e - s for s, e in merged_intervals(intervals))
