"""Percentiles and spreads, one definition for every cell."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the
    smallest value with at least q% of the sample at or below it. A tail
    of all the ops of a window is one of those ops, not an
    interpolation."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of nothing")
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def iqr_share(values) -> float:
    """Distance between the first and third quartile over the median,
    as the driver takes it (statistics.quantiles, n=4)."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
