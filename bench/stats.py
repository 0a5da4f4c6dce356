"""Percentile and spread arithmetic of the benchmark."""
from __future__ import annotations

import math
import statistics

import numpy as np


def nearest_rank(values, pct: int) -> float:
    """Nearest-rank ``pct``-th percentile: the smallest value with at least
    ``pct`` percent of the samples at or below it (the ``ceil(pct n /
    100)``-th order statistic, in integer arithmetic as the program's
    ``SimResult.p99`` does).  Infinite samples (requests that failed or
    never finished) sort last.  ``nan`` for no samples."""
    a = np.asarray(values, dtype=np.float64).ravel()
    n = a.size
    if not n:
        return math.nan
    if not 0 < pct <= 100:
        raise ValueError(f"percentile {pct} outside (0, 100]")
    k = (pct * n + 99) // 100 - 1
    return float(np.partition(a, k)[k])


def quartile_spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / q2
