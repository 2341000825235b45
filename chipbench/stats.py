"""Arithmetic the metrics share: percentiles, the spread the bounds are set
from, and the apportionment that gives every seed the same work."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order statistics
    (numpy's default method), over all the values given."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of no samples")
    pos = q * (len(s) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def spread(values) -> float:
    """Interquartile distance as a share of the median, with the quartiles of
    `statistics.quantiles(values, n=4)` — what the driver's bounds are held to."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def apportion(weights, total: int) -> list:
    """`total` items split over `weights` by largest remainder: the same
    counts for every seed, so that seeds change the order and not the work."""
    norm = sum(weights)
    exact = [w * total / norm for w in weights]
    counts = [math.floor(x) for x in exact]
    by_remainder = sorted(range(len(weights)), key=lambda i: (counts[i] - exact[i], i))
    for i in by_remainder[:total - sum(counts)]:
        counts[i] += 1
    return counts
