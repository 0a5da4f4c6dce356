"""Nearest-rank 99th percentile of the latency of every request offered in
the window, from due time to completion, in ms.  A request that failed or
never finished counts as infinitely late."""
from bench.stats import nearest_rank


def read(run):
    return 1e3 * nearest_rank(run.latency_s, 99)
