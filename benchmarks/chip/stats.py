"""Percentile and spread arithmetic of the benchmark."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of the sample at or below it (``q`` in (0, 100])."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0.0 < q <= 100.0:
        raise ValueError(f"percentile {q} outside (0, 100]")
    xs = sorted(values)
    return xs[max(1, math.ceil(q / 100.0 * len(xs))) - 1]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles``' default method)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
