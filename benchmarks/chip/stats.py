"""Percentiles as the end-to-end metrics define them."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest rank: the smallest value with at least ``p`` percent of all
    values at or below it.  Every value counts, so a tail is the tail of
    all requests, and no value is interpolated."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    return float(xs[max(math.ceil(p / 100.0 * len(xs)), 1) - 1])
