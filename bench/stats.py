"""Percentile and spread arithmetic, in one place and tested."""

from __future__ import annotations

import math
import statistics


def percentile(values: list, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between closest
    ranks (numpy's default): p = q/100 * (n-1)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (q / 100.0) * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def iqr_share(values: list) -> float:
    """Distance between the first and third quartile as a share of the
    median, with Python's ``statistics.quantiles(values, n=4)``: the spread
    the bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
