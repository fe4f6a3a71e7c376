"""Latency percentiles and rates over all of a window's queries."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank q-th percentile: the smallest value that at least
    q% of the values do not exceed."""
    if not values:
        raise ValueError("no values")
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def rate(count: int, seconds: float) -> float:
    """Completions per second over the whole window."""
    return count / seconds


def mean(values: Sequence[float]) -> float:
    return sum(values) / len(values)
