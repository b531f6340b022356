"""Percentile, rate and spread arithmetic, kept with the benchmark."""

from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between
    the two nearest order statistics. Raises on an empty list: a metric
    with nothing to read is left out, never reported as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def rate(count: int, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over no time")
    return count / seconds


def spread(values) -> float:
    """Distance between the first and third quartile
    (`statistics.quantiles(values, n=4)`) as a share of the median: the
    measure the bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
