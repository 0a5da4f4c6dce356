"""How long a host pool thread works on a request: nearest-rank median of
the engine's ``engine.suffix`` spans (copy at the cut, host stages, sync,
completion record) that start in the traced interval, in ms."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "engine.suffix")
