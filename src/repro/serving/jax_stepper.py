"""JAX-native Lindley stepper: the vectorized fast path on-device.

``JaxStepper`` is ``RuntimeSimulator`` with the two sequential-bottleneck
recurrences -- the TPU FCFS Lindley pass and the single-core CPU-pool
passes -- evaluated by a jitted chunked max-plus scan instead of the NumPy
guess/classify/fixpoint of ``_server_ends``.  Everything order- or
integer-valued (routing, SRAM miss replay, recording, cache stamps,
multi-core CPU heaps) is inherited unchanged from the parent, so the two
backends differ *only* in float rounding of the busy-period recurrence.

Contract (ROADMAP standing invariant): the NumPy paths are the bitwise
references; the JAX paths are **statistically equivalent** -- float32
kernels, means/p99 within tolerance on seeded replicas, identical integer
observables.  The kernel works in *delay space* precisely to make float32
safe: absolute completion clocks (thousands of seconds) would lose the
microsecond-scale service times to cancellation, while queueing delays and
inter-arrival gaps stay small.

Mathematics
-----------
The FCFS busy-period recurrence over enqueue times ``tau`` and services
``s`` is ``end[j] = max(tau[j], end[j-1]) + s[j]``.  Substituting the
*delay* ``d[j] = end[j] - tau[j]`` and the gap ``g[j] = tau[j] -
tau[j-1]`` gives

    d[j] = max(0, d[j-1] - g[j]) + s[j]
         = max(A[j], d[j-1] + B[j]),   A[j] = s[j],  B[j] = s[j] - g[j].

Each request is thus an element of the max-plus affine semigroup
``f(x) = max(A, x + B)`` with the associative composition

    (f2 . f1)(x) = max(max(A2, A1 + B2), x + (B1 + B2)).

XLA:CPU runs a flat ``lax.scan`` an order of magnitude slower than NumPy's
fused cumulative kernels, so the evaluation is blocked: the trace reshapes
into ``C`` contiguous chunks of length ``L``; within each chunk the prefix
compositions collapse to one ``cumsum`` plus one associative ``cummax``
along the contiguous axis (``pB = cumsum(B)``, ``pA = pB + cummax(A -
pB)`` -- the classic Lindley identity, float32-safe because per-chunk
sums stay small); a short sequential scan combines the ``C`` chunk
carries; a fused elementwise resolve produces every delay.  The grid is
tuned for XLA:CPU (wide chunks, ``cumsum``/``associative_scan`` on the
minor axis); on an accelerator the same kernel shape parallelizes across
chunks and replicas.

``JaxStepper.run_trace_replicas`` is the Monte-Carlo engine this buys:
``R`` per-model service-jitter replicas of one arrival order resolve in a
handful of device calls -- arrival order, routing, and the SRAM miss
pattern are shared (service jitter cannot reorder FCFS enqueues), so they
are hoisted out of the replica loop, while the NumPy stepper must pay the
full pipeline per replica.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.serving.simulator import RuntimeSimulator
from repro.serving.workload import Trace

__all__ = ["JaxStepper", "ReplicaStats", "lindley_ends"]

# Identity element of the max-plus affine semigroup: max(NEG, x + 0) == x
# for every finite float32 x.  Finite (not -inf) so composition arithmetic
# on padded lanes never produces inf - inf = nan.
_NEG = np.float32(-3e38)


def _grid(n: int) -> tuple[int, int]:
    """Chunk grid ``(C, L)`` with ``C * L >= n``, both powers of two.

    Tuned on XLA:CPU: C ~ 2048 keeps the within-chunk cumulative kernels
    on long contiguous rows (where XLA's cumsum/associative_scan are
    fastest) while the chunk-carry combine stays a short scan.  Power-of-
    two padding bounds the set of compiled shapes at ~log2(N).
    """
    padded = 1 << max(10, (n - 1).bit_length())
    c = min(2048, max(1, padded // 512))
    return c, padded // c


@partial(jax.jit, static_argnames=("c", "l"))
def _delays_kernel(a, b, x_init, c: int, l: int):
    """Batched Lindley delays: ``[R, c*l]`` elements -> ``[R, c*l]``.

    ``x_init`` is the per-replica initial backlog ``free0 - tau[0]``,
    shape ``[R]``.  Three stages (see module docstring): within-chunk
    prefix compositions (cumsum + associative cummax on the contiguous
    axis), a sequential combine over the C chunk carries, and the fused
    elementwise resolve.
    """
    a2 = a.reshape(-1, c, l)
    b2 = b.reshape(-1, c, l)
    pb = jnp.cumsum(b2, axis=2)
    pa = pb + jax.lax.associative_scan(jnp.maximum, a2 - pb, axis=2)

    # Chunk carries: x entering chunk k = chunks 0..k-1 applied to x_init.
    full_a = jnp.moveaxis(pa[:, :, -1], 1, 0)  # [C, R]
    full_b = jnp.moveaxis(pb[:, :, -1], 1, 0)

    def carry_step(x, elem):
        ca, cb = elem
        return jnp.maximum(ca, x + cb), x

    _, xc = jax.lax.scan(carry_step, x_init, (full_a, full_b))
    xc = jnp.moveaxis(xc, 0, 1)  # [R, C]

    d = jnp.maximum(pa, xc[:, :, None] + pb)
    return d.reshape(a.shape)


def _elements(enqueue: np.ndarray, service: np.ndarray):
    """Host-side float32 (A, B) build from float64 columns.

    A, B, and the initial backlog are all *small* (services and gaps);
    the cast here is the only precision loss in the pass -- the absolute
    clock never enters the kernel.
    """
    gaps = np.empty_like(enqueue)
    gaps[0] = 0.0
    np.subtract(enqueue[1:], enqueue[:-1], out=gaps[1:])
    return service.astype(np.float32), (service - gaps).astype(np.float32)


def _pad(arr: np.ndarray, size: int, fill) -> np.ndarray:
    if arr.shape[-1] == size:
        return arr
    out = np.full(arr.shape[:-1] + (size,), fill, dtype=arr.dtype)
    out[..., : arr.shape[-1]] = arr
    return out


def lindley_ends(
    enqueue: np.ndarray, service: np.ndarray, free0: float
) -> np.ndarray:
    """FCFS completion times via the jitted max-plus scan.

    Drop-in for ``simulator._server_ends`` under the statistical contract:
    delays are float32, the absolute clock is restored in float64 on the
    host (``ends = tau + d``), padded tail lanes carry identity elements
    so real prefixes are unaffected.
    """
    n = enqueue.size
    if n == 0:
        return np.empty(0)
    a, b = _elements(enqueue, service)
    c, l = _grid(n)
    a = _pad(a, c * l, _NEG)[None]
    b = _pad(b, c * l, np.float32(0.0))[None]
    x_init = np.asarray([free0 - enqueue[0]], dtype=np.float32)
    d = np.asarray(_delays_kernel(a, b, x_init, c, l))[0, :n]
    return enqueue + d.astype(np.float64)


@partial(jax.jit, static_argnames=("c", "l", "n_models"))
def _tpu_replicas_kernel(
    base, miss_load, g, tm, scales, x_init, c: int, l: int, n_models: int,
):
    """Fused TPU stage for R replicas: in-graph service build + delays +
    per-model delay sums + busy time.  ``scales`` is ``[R, n_models]``
    (per-model jitter -- the ``Trace.service_scale`` semantics applied
    model-wise), everything else is one shared padded column.

    Padding needs no mask: dead lanes carry ``base = miss_load = g = 0``
    (so ``svc = 0`` -- invisible to ``busy``) and ``tm = n_models``, whose
    one-hot row is all-zero -- invisible to the per-model sums.  Their
    element ``f(x) = max(0, x)`` is not the semigroup identity, but dead
    lanes sit strictly *after* every real request, so no real prefix ever
    composes through one.
    """
    svc = base * scales[:, tm] + miss_load  # [R, P]
    d = _delays_kernel(svc, svc - g, x_init, c, l)
    # HIGHEST: a DEFAULT float32 dot runs as one bf16 pass on a TPU, far
    # outside the ~1e-4 relative contract on the per-model means.
    sums = jnp.dot(
        d, jax.nn.one_hot(tm, n_models, dtype=d.dtype),
        precision=jax.lax.Precision.HIGHEST,
    )
    busy = svc.sum(axis=1)
    return d, sums, busy


@partial(jax.jit, static_argnames=("c", "l"))
def _cpu_replicas_kernel(
    d_tpu, sel, g_host, svc, x0_host, c: int, l: int
):
    """Fused single-core CPU-pool stage for one model across R replicas.

    The pool's enqueue column is ``t_in = ends[sel] + out_xfer``; only its
    *gap* structure matters, which splits into the shared host part
    (enqueue-time diffs) plus the replica-dependent part (TPU delay
    diffs) -- both small, both float32-safe.  ``svc`` is the replica's
    constant service ``s_cpu * scale_r`` (per-model jitter), ``x0_host``
    the shared part of the initial backlog ``-(enq[sel[0]] + out_xfer)``
    (an idle pool at t=0); the replica part is gathered in-graph.
    """
    dsel = d_tpu[:, sel]  # [R, n_i]
    dd = jnp.diff(dsel, axis=1, prepend=dsel[:, :1])
    g = g_host[None, :] + dd
    x_init = x0_host - dsel[:, 0]
    pad_n = c * l
    n_i = sel.shape[0]
    a = jnp.full((dsel.shape[0], pad_n), _NEG)
    a = a.at[:, :n_i].set(svc[:, None])
    b = jnp.zeros((dsel.shape[0], pad_n))
    b = b.at[:, :n_i].set(svc[:, None] - g)
    d = _delays_kernel(a, b, x_init, c, l)[:, :n_i]
    return d, d.sum(axis=1)


@dataclasses.dataclass(frozen=True)
class ReplicaStats:
    """Per-replica summaries from ``JaxStepper.run_trace_replicas``.

    ``mean_latency[r, m]`` matches ``SimResult.mean_latency(m)`` of the
    NumPy stepper run on the same replica's trace to float32 tolerance;
    ``counts``/``misses`` are exact and shared across replicas (service
    jitter cannot change arrival order or the SRAM access sequence).
    """

    mean_latency: np.ndarray   # [R, n_models] float64
    counts: np.ndarray         # [n_models] int64
    misses: np.ndarray         # [n_models] int64
    tpu_busy: np.ndarray       # [R] float64


class JaxStepper(RuntimeSimulator):
    """``RuntimeSimulator`` with on-device Lindley recurrences.

    Overrides exactly one hook -- ``_lindley`` -- so every other mechanism
    (scalar ``step`` fallback, deferred disciplines, SRAM replay, heap CPU
    pools, recording) is the parent's, behaviorally *and* textually.
    Integer observables are bitwise identical to the NumPy stepper; float
    observables agree to float32 tolerance (``tests/test_jax_sim.py``).
    """

    def _lindley(
        self, enqueue: np.ndarray, service: np.ndarray, free0: float
    ) -> np.ndarray:
        return lindley_ends(enqueue, service, free0)

    # -- Monte-Carlo replica engine ---------------------------------------
    def run_trace_replicas(
        self, trace: Trace, scales: np.ndarray
    ) -> ReplicaStats:
        """Resolve ``R`` per-model service-jitter replicas of one trace.

        ``scales`` is ``[R, n_models]``: replica r scales every request of
        model m by ``scales[r, m]`` (measurement-uncertainty Monte Carlo
        over the profiled service times -- the ``Trace.service_scale``
        column ``scales[r, trace.model_idx]`` gives the identical model on
        the NumPy stepper, which is exactly what the equivalence self-
        check replays).  Requirements: a fresh simulator (no prior
        offers), FCFS discipline, unit-scale sorted trace, and k <= 1 CPU
        pools -- the regime where both stages are pure Lindley scans.  The
        arrival order, routing, enqueue clock, and SRAM miss pattern are
        replica-invariant and hoisted; only the busy-period scans and the
        summary reductions run per replica (in one device call per stage).
        """
        scales = np.asarray(scales, dtype=np.float64)
        if scales.ndim != 2 or scales.shape[1] != self.n:
            raise ValueError("scales must be [n_replicas, n_models]")
        if self._disc is not None:
            raise ValueError("replica engine supports FCFS plans only")
        if any(len(pool) > 1 for pool in self._cpu_pools):
            raise ValueError("replica engine supports k<=1 CPU pools only")
        if self.tpu_free != 0.0 or self.tpu_busy != 0.0:
            raise ValueError("replica engine requires a fresh simulator")
        if not trace.is_sorted:
            raise ValueError("run_trace_replicas requires a sorted Trace")
        if not trace.scale_is_unit:
            raise ValueError(
                "per-request service_scale and per-model replica scales "
                "would compose ambiguously; pass a unit-scale trace"
            )
        n_req = len(trace)
        r_rep = scales.shape[0]
        m = trace.model_idx
        arr = trace.arrival
        has_tpu = self._part_arr > 0
        has_cpu = self._part_arr < self._points_arr

        counts = np.bincount(m, minlength=self.n)
        mean_lat = np.zeros((r_rep, self.n))
        misses_out = np.zeros(self.n, dtype=np.int64)
        busy = np.zeros(r_rep)
        if n_req == 0:
            return ReplicaStats(mean_lat, counts, misses_out, busy)

        # -- shared TPU-stage structure (replica-invariant) --------------
        if bool(has_tpu.all()):
            ti, tm = None, m
        else:
            ti = np.flatnonzero(has_tpu[m])
            tm = m[ti]
        d_tpu = None
        scales32 = scales.astype(np.float32)
        if tm.size:
            arr_t = arr if ti is None else arr[ti]
            enq = arr_t + self._in_xfer_arr[tm]
            last = np.full(self.n, -1, dtype=np.int64)
            last[tm] = np.arange(tm.size)
            first = np.full(self.n, -1, dtype=np.int64)
            first[tm[::-1]] = np.arange(tm.size - 1, -1, -1)
            miss, _ = self._replay_lru(tm, first, last)
            misses_out += np.bincount(tm[miss], minlength=self.n)

            gaps = np.empty_like(enq)
            gaps[0] = 0.0
            np.subtract(enq[1:], enq[:-1], out=gaps[1:])
            base = self._s_tpu_arr[tm].astype(np.float32)
            miss_load = np.where(miss, self._t_load_arr[tm], 0.0).astype(
                np.float32
            )
            c, l = _grid(tm.size)
            pad_n = c * l
            x_init = np.full(
                r_rep, 0.0 - enq[0], dtype=np.float32
            )
            d_tpu, sums, busy32 = _tpu_replicas_kernel(
                jnp.asarray(_pad(base, pad_n, np.float32(0.0))),
                jnp.asarray(_pad(miss_load, pad_n, np.float32(0.0))),
                jnp.asarray(_pad(gaps.astype(np.float32), pad_n,
                                 np.float32(0.0))),
                jnp.asarray(
                    _pad(tm.astype(np.int32), pad_n, np.int32(self.n))
                ),
                jnp.asarray(scales32),
                jnp.asarray(x_init),
                c, l, self.n,
            )
            busy += np.asarray(busy32, dtype=np.float64)
            # TPU-stage latency = in_xfer + delay (enqueue - arrival is
            # exactly the input transfer).
            sums_np = np.asarray(sums, dtype=np.float64)
            tpu_counts = np.bincount(tm, minlength=self.n)
            nz = tpu_counts > 0
            mean_lat[:, nz] += (
                self._in_xfer_arr[nz][None, :]
                + sums_np[:, nz] / tpu_counts[nz][None, :]
            )

        # -- per-model single-core CPU pools ------------------------------
        for i in np.flatnonzero(has_cpu).tolist():
            if ti is None:
                sel = np.flatnonzero(m == i)
                sel_t = sel
            else:
                sel = np.flatnonzero(m == i)
                # Position of model i's requests inside the TPU trace (all
                # of model i is TPU-routed when has_tpu[i]).
                sel_t = np.flatnonzero(tm == i) if has_tpu[i] else None
            if sel.size == 0:
                continue
            svc_cpu = (self._s_cpu[i] * scales[:, i]).astype(np.float32)
            if has_tpu[i]:
                # t_in = enq[sel_t] + d[sel_t] + out_xfer: split gaps into
                # the shared enqueue part and the replica delay part.
                enq_i = enq[sel_t]
                g_host = np.empty_like(enq_i)
                g_host[0] = 0.0
                np.subtract(enq_i[1:], enq_i[:-1], out=g_host[1:])
                c2, l2 = _grid(sel_t.size)
                x0_host = np.float32(
                    0.0 - (enq_i[0] + self._out_eff_arr[i])
                )
                _, cpu_sums = _cpu_replicas_kernel(
                    d_tpu,
                    jnp.asarray(sel_t.astype(np.int32)),
                    jnp.asarray(g_host.astype(np.float32)),
                    jnp.asarray(svc_cpu),
                    x0_host,
                    c2, l2,
                )
                # Total latency = in_xfer + d_tpu + out_xfer + d_cpu.
                mean_lat[:, i] += self._out_eff_arr[i] + np.asarray(
                    cpu_sums, dtype=np.float64
                ) / sel.size
            else:
                # Full-CPU route: the pool's enqueue column is the arrival
                # itself, shared across replicas.
                arr_i = arr[sel]
                a32, b32 = _elements(arr_i, np.zeros(sel.size))
                c2, l2 = _grid(sel.size)
                pad_n = c2 * l2
                g_i = (a32 - b32)  # recovers the float32 gaps
                a_k = np.full((r_rep, pad_n), _NEG, dtype=np.float32)
                b_k = np.zeros((r_rep, pad_n), dtype=np.float32)
                a_k[:, : sel.size] = svc_cpu[:, None]
                b_k[:, : sel.size] = svc_cpu[:, None] - g_i[None, :]
                x0 = np.full(r_rep, 0.0 - arr_i[0], dtype=np.float32)
                d_cpu = np.asarray(
                    _delays_kernel(
                        jnp.asarray(a_k), jnp.asarray(b_k),
                        jnp.asarray(x0), c2, l2,
                    )
                )[:, : sel.size]
                mean_lat[:, i] += d_cpu.sum(axis=1) / sel.size

        return ReplicaStats(mean_lat, counts, misses_out, busy)
