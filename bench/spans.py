"""The serving engine's spans (``engine.*``, ``serving/engine.py``) in a
trace reduced by ``bench/tracing.py``.

``tracing.load`` keeps every host event by thread, and spans are found
there by name: every Python thread carries the same name in the trace.
The accelerator worker's thread is the one that holds ``engine.prefix``
spans; ``engine.launch`` and ``engine.sync`` are read on that thread only,
since the host pools' suffixes write spans of those names too.  Nothing
here finds anything in the trace of a program without these spans.
"""
from __future__ import annotations

import numpy as np

from bench import tracing
from bench.stats import nearest_rank

PREFIX = "engine.prefix"
PREFIX_CHILDREN = ("engine.h2d", "engine.launch", "engine.sync")


def durations_ms(ev: tracing.Events, name: str) -> np.ndarray:
    """Durations in ms of the spans named ``name`` that start inside the
    traced interval."""
    lo, hi = ev.window
    return np.array([(e - s) * 1e-6 for events in ev.host.values()
                     for s, e, n in events if n == name and lo <= s < hi])


def median_ms(run, name: str) -> float | None:
    """Nearest-rank median of ``durations_ms``; ``None`` without a trace or
    without such spans in it."""
    if run.events is None:
        return None
    d = durations_ms(run.events, name)
    return nearest_rank(d, 50) if d.size else None


def _length_ns(intervals, lo: float, hi: float) -> float:
    m = tracing.merged(intervals, lo, hi)
    return float((m[:, 1] - m[:, 0]).sum()) if len(m) else 0.0


def idle_split(ev: tracing.Events) -> dict[str, float] | None:
    """Seconds of the traced interval in which no XLA op runs on the
    device, by the innermost accelerator-worker span open then:
    ``engine.h2d``, ``engine.launch``, ``engine.sync``, ``engine.prefix``
    (open, none of its children) and ``none`` (no prefix open: no request
    at the device's door).  ``None`` where the trace has no device op or no
    ``engine.prefix`` span.

    The idle time under a set of spans S is |ops u S| - |ops| over the
    interval: the part of S that no device op covers."""
    worker = [events for events in ev.host.values()
              if any(n == PREFIX for _, _, n in events)]
    if not ev.ops or not worker:
        return None
    lo, hi = ev.window
    spans = {name: [x for events in worker for x in events if x[2] == name]
             for name in (PREFIX,) + PREFIX_CHILDREN}
    busy = _length_ns(ev.ops, lo, hi)

    def idle_under(intervals):
        return _length_ns(ev.ops + intervals, lo, hi) - busy

    split = {name: idle_under(spans[name]) for name in PREFIX_CHILDREN}
    in_prefix = idle_under(spans[PREFIX])
    split[PREFIX] = in_prefix - idle_under(
        [x for name in PREFIX_CHILDREN for x in spans[name]])
    split["none"] = (hi - lo) - busy - in_prefix
    return {k: v * 1e-9 for k, v in split.items()}
