"""Percentiles with their sample counts, and the spread the bounds are set from."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the two nearest ranks of the sorted sample; None for an empty one."""
    if not values:
        return None
    xs = sorted(values)
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = math.ceil(rank)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile: a
    tail wants ten or more (choosing-metrics, section 1)."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile, as
    ``statistics.quantiles(values, n=4)`` gives them, over the median:
    the spread the contract sets a bound from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
