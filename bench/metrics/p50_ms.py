"""Median latency of every request offered in the window, from the time it
was due to its completion record, in ms (nearest rank).  A request that
failed or never finished counts as infinitely late."""
from bench.stats import nearest_rank


def read(run):
    return 1e3 * nearest_rank(run.latency_s, 50)
