"""Open-loop request schedules for the benchmark's traffic mixes.

The arrival processes follow the program's ``repro.serving.workload``
(``poisson_trace``: Poisson arrivals; ``mmpp_trace``: a two-state
Markov-modulated Poisson process that starts in its normal state), copied
here so that no change to the program moves the yardstick.  One thing
differs: every seed offers the same work.  A window of ``seconds`` at
``rate_rps`` holds exactly ``round(rate_rps * seconds)`` requests, each
tenant gets its Zipf share of them by largest remainder, and the gaps
between arrivals are the same stratified draw of the exponential law in
an order set by the seed.  Two seeds therefore differ in the order of
tenants and gaps, not in how much work they offer, which keeps the
spread between runs down to what the system does.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One window's requests, sorted by due time."""

    due: np.ndarray      # seconds after the window opens
    tenant: np.ndarray   # tenant index, in the configuration's order
    item: np.ndarray     # index into that tenant's input pool

    def __len__(self) -> int:
        return int(self.due.size)


def zipf_shares(n: int, s: float) -> np.ndarray:
    """Request shares of ``n`` tenants in popularity order, Zipf(s)."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def apportion(total: int, shares: np.ndarray) -> np.ndarray:
    """Split ``total`` into integer counts by largest remainder."""
    exact = total * np.asarray(shares, dtype=np.float64)
    counts = np.floor(exact).astype(np.int64)
    rest = total - int(counts.sum())
    # Stable on ties: the more popular tenant gets the spare request.
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts


def _unit_exponential_gaps(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` unit-mean exponential gaps: the law's quantiles at the
    midpoints of ``n`` equal strata, in a seeded order."""
    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    return rng.permutation(-np.log1p(-q))


def poisson_arrivals(n: int, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` Poisson-like arrival times in [0, seconds): ``n + 1``
    stratified exponential gaps scaled to span the window."""
    gaps = _unit_exponential_gaps(n + 1, rng)
    return np.cumsum(gaps[:n]) * (seconds / gaps.sum())


def mmpp_arrivals(
    n: int,
    seconds: float,
    rng: np.random.Generator,
    *,
    burst_factor: float,
    mean_normal_s: float,
    mean_burst_s: float,
) -> np.ndarray:
    """``n`` arrival times in [0, seconds) of a two-state MMPP.

    The modulating chain starts in its normal state and holds each state
    for an exponential time of the given mean; the burst state's rate is
    ``burst_factor`` times the normal one.  The ``n`` arrivals are the
    stratified unit points of ``poisson_arrivals`` carried through the
    inverse of the chain's cumulative intensity over the window.
    """
    if burst_factor < 0 or mean_normal_s <= 0 or mean_burst_s <= 0:
        raise ValueError("MMPP needs burst_factor >= 0 and positive sojourns")
    edges, level, t, burst = [0.0], [], 0.0, False
    while t < seconds:
        t = min(seconds, t + float(rng.exponential(
            mean_burst_s if burst else mean_normal_s)))
        edges.append(t)
        level.append(burst_factor if burst else 1.0)
        burst = not burst
    cum = np.concatenate([[0.0], np.cumsum(np.diff(edges) * np.asarray(level))])
    if cum[-1] <= 0:
        raise ValueError("MMPP intensity is zero over the whole window")
    unit = poisson_arrivals(n, 1.0, rng) * cum[-1]
    return np.interp(unit, cum, np.asarray(edges))


def schedule(mix: dict, n_tenants: int, seconds: float, seed: int) -> Schedule:
    """The seeded schedule of one window of ``mix`` (a traffic file)."""
    rate = float(mix["rate_rps"])
    if rate <= 0 or seconds <= 0:
        raise ValueError(f"rate {rate} and seconds {seconds} must be positive")
    n = int(round(rate * seconds))
    rng = np.random.default_rng(seed)
    kind = mix["arrivals"]
    if kind == "poisson":
        due = poisson_arrivals(n, seconds, rng)
    elif kind == "mmpp":
        due = mmpp_arrivals(
            n, seconds, rng,
            burst_factor=float(mix["burst_factor"]),
            mean_normal_s=float(mix["mean_normal_s"]),
            mean_burst_s=float(mix["mean_burst_s"]),
        )
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    counts = apportion(n, zipf_shares(n_tenants, float(mix["zipf_s"])))
    tenant = rng.permutation(np.repeat(np.arange(n_tenants), counts))
    item = rng.integers(0, int(mix["pool_size"]), size=n)
    return Schedule(due=due, tenant=tenant, item=item)
