"""Percentiles and spreads as the benchmark takes them."""
from __future__ import annotations

import math
import statistics
from typing import Sequence

import numpy as np


def percentile(x: Sequence[float], q: float) -> float:
    """q-th percentile, linear between neighbours; infinite where it falls
    on or next to an infinite value (a request that never finished counts
    as infinitely late)."""
    x = np.sort(np.asarray(x, float))
    if not len(x):
        return math.nan
    i = (len(x) - 1) * q / 100
    lo, hi = int(math.floor(i)), int(math.ceil(i))
    if math.isinf(x[hi]):
        return math.inf
    return float(x[lo] + (x[hi] - x[lo]) * (i - lo))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile, as
    ``statistics.quantiles(values, n=4)`` gives them, over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
