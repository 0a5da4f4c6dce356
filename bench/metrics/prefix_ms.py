"""How long the accelerator worker holds a request: nearest-rank median of
the engine's ``engine.prefix`` spans (input copy, stage launches, sync,
handoff) that start in the traced interval, in ms."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "engine.prefix")
