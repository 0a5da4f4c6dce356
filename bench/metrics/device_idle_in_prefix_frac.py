"""Share of the traced interval in which no XLA op runs on the device while
the accelerator worker holds a request (an ``engine.prefix`` span is
open): the part of ``device_idle_frac`` spent with a request at the
device's door, which host work can shorten, against the part with none
waiting."""
from bench.spans import idle_split


def read(run):
    split = None if run.events is None else idle_split(run.events)
    if split is None:
        return None
    lo, hi = run.events.window
    return (sum(split.values()) - split["none"]) / ((hi - lo) * 1e-9)
