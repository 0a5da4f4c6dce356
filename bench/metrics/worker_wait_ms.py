"""How long a request waits in the accelerator worker's inbox: nearest-rank
median of the engine's ``engine.worker_wait`` spans (submit to the worker
taking the request) that start in the traced interval, in ms."""
from bench.spans import median_ms


def read(run):
    return median_ms(run, "engine.worker_wait")
