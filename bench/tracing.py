"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The benchmark opens a ``bench.traced`` annotation on the generator thread
when the traced interval starts and closes it when the interval ends; every
number here is taken inside that interval.  On the device plane, busy time
is the union of the ``XLA Ops`` events; each ``XLA Modules`` event is one
execution of a stage program.  Host threads are read only to say what the
host was doing while the device sat idle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

import numpy as np

MARK = "bench.traced"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
HOST_XLA_CPU_PREFIX = "tf_XLAPjRtCpuClient"


@dataclasses.dataclass
class Events:
    """Events of one trace, in nanoseconds on the profiler's clock."""

    ops: list[tuple[float, float, str]]        # device XLA ops
    modules: list[tuple[float, float, str]]    # device program executions
    host: dict[str, list[tuple[float, float, str]]]  # by host thread
    window: tuple[float, float]                # the ``bench.traced`` span
    n_devices: int = 1


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(log_dir: str) -> Events:
    """Read the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(log_dir))
    ops, modules, host, window, devices = [], [], defaultdict(list), None, set()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.add(plane.name)
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest.extend((e.start_ns, e.end_ns, e.name) for e in line.events)
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                key = f"{line.name or 'thread'}#{i}"
                for e in line.events:
                    if e.name == MARK:
                        window = (e.start_ns, e.end_ns)
                    elif e.duration_ns > 0:
                        host[key].append((e.start_ns, e.end_ns, e.name))
    if window is None:
        raise ValueError(f"trace under {log_dir} has no {MARK!r} span")
    ops.sort()
    modules.sort()
    return Events(ops=ops, modules=modules, host=dict(host), window=window,
                  n_devices=max(1, len(devices)))


def merged(intervals, lo: float, hi: float) -> np.ndarray:
    """Union of ``(start, end, ...)`` intervals clipped to [lo, hi], as a
    sorted array of disjoint ``(start, end)`` rows."""
    a = np.array([(max(s, lo), min(e, hi)) for s, e, *_ in intervals
                  if e > lo and s < hi], dtype=np.float64).reshape(-1, 2)
    if not len(a):
        return a
    a = a[np.argsort(a[:, 0], kind="stable")]
    out = [list(a[0])]
    for s, e in a[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def gaps(busy: np.ndarray, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] between the rows of ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_label(gap: tuple[float, float],
               host: dict[str, list[tuple[float, float, str]]]) -> str:
    """The host event that overlaps ``gap`` the most, as ``thread: name``;
    ``host idle`` where no traced host event overlaps it."""
    lo, hi = gap
    best, label = 0.0, "host idle"
    for thread, events in host.items():
        for s, e, name in events:
            ov = min(e, hi) - max(s, lo)
            if ov > best:
                best, label = ov, f"{thread.split('/')[0].split('#')[0]}: {name}"
    return label


def op_kind(name: str) -> str:
    """An XLA op's name up to its layout: ``%fusion.3 = bf16[1,8,8,16]``."""
    return name.split("{", 1)[0].strip()


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    device_ops: list[list]     # [name, seconds], most time first
    idle_gaps: list[list]      # [host activity, seconds], longest first
    has_device: bool = True    # whether the trace holds any device op

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(ev: Events, top: int = 10) -> Reduced:
    lo, hi = ev.window
    busy = merged(ev.ops, lo, hi)
    busy_ns = float((busy[:, 1] - busy[:, 0]).sum()) if len(busy) else 0.0
    per_op: dict[str, float] = defaultdict(float)
    for s, e, name in ev.ops:
        if e > lo and s < hi:
            per_op[op_kind(name)] += (min(e, hi) - max(s, lo)) * 1e-9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    idle = [[host_label(g, ev.host), (g[1] - g[0]) * 1e-9] for g in longest]
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / ev.n_devices,
                   device_ops=[[k, v] for k, v in ops], idle_gaps=idle,
                   has_device=bool(ev.ops))


def host_xla_cpu_busy_s(ev: Events) -> float:
    """Seconds of XLA:CPU execution inside the window, summed over the
    PjRt CPU client's threads (each thread's events as a union)."""
    lo, hi = ev.window
    total = 0.0
    for thread, events in ev.host.items():
        if thread.startswith(HOST_XLA_CPU_PREFIX):
            m = merged(events, lo, hi)
            total += float((m[:, 1] - m[:, 0]).sum()) if len(m) else 0.0
    return total * 1e-9
