"""How long a request waits for a host pool thread: nearest-rank median of
the engine's ``engine.pool_wait`` spans (handoff to the start of the
suffix) that start in the traced interval, in ms."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "engine.pool_wait")
