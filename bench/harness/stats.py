"""Order statistics for the metric readers and the spread of a set of
runs."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of every value."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile
    (``statistics.quantiles(n=4)``) as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
