"""Real-execution multi-tenant engine (Section IV plumbing).

Executes actual JAX computations with each half of a tenant's split on the
device it claims.  A single global accelerator worker thread drains an FCFS
queue of prefix executions on the accelerator (``jax.devices()[0]``);
per-model host thread pools run the suffixes on the host CPU device
(``jax.devices("cpu")[0]``).  The activation crosses at the cut by an
explicit ``jax.device_put``, and the all-host (partition 0) and
all-accelerator (partition P) plans follow the same rule.  Each segment's
params are committed to the device that runs it under the current plan;
``set_plan`` moves only the segments whose side of the cut changed.  In a
CPU-only process both devices are the CPU.  Latency *validation* against
the paper's edge testbed is done by the discrete-event simulator, which
models that platform's timing.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Sequence

import jax

from repro.core.planner import Plan

# A partitioned executable model's segment: pure (params, activation) ->
# activation.  The engine jits it and commits ``params`` to its device.
SegmentFn = Callable[[Any, Any], Any]


@dataclasses.dataclass
class ExecutableModel:
    """A chain of pure segment functions, their host (NumPy) params, and an
    input synthesizer."""

    name: str
    segments: tuple[SegmentFn, ...]
    params: tuple[Any, ...]            # one host params pytree per segment
    make_input: Callable[[int], Any]   # seed -> host model input

    @property
    def num_partition_points(self) -> int:
        return len(self.segments)


@dataclasses.dataclass
class CompletedRequest:
    model_idx: int
    submit_time: float
    done_time: float
    output: Any
    # The exception that aborted this request's execution, or None on
    # success (``output`` is None for errored records).  Errors surface as
    # completed records instead of vanishing inside worker threads, so
    # ``drain()`` always terminates and the caller sees every failure.
    error: BaseException | None = None
    # The device that held the prefix's output activation; None when the
    # plan ran no prefix (partition 0) or the prefix failed.
    prefix_device: jax.Device | None = None

    @property
    def latency(self) -> float:
        return self.done_time - self.submit_time

    @property
    def ok(self) -> bool:
        return self.error is None


def _host_device() -> jax.Device:
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as exc:
        raise RuntimeError(
            "ServingEngine runs suffixes on the host CPU device, but this "
            "process has no CPU backend; include 'cpu' in JAX_PLATFORMS"
        ) from exc


class _TpuWorker(threading.Thread):
    """Single global FCFS worker executing prefixes on the accelerator."""

    def __init__(self, engine: "ServingEngine"):
        super().__init__(daemon=True, name="tpu-worker")
        self.engine = engine
        self.inbox: "queue.Queue" = queue.Queue()

    def run(self) -> None:
        while True:
            item = self.inbox.get()
            if item is None:
                return
            self.engine._run_prefix(*item)


class ServingEngine:
    """Multi-tenant collaborative-inference engine over executable models.

    Raises at construction when the process has no CPU backend: the host
    suffix never silently runs on the accelerator.
    """

    def __init__(
        self,
        models: Sequence[ExecutableModel],
        plan: Plan,
        k_max: int,
    ):
        self.models = list(models)
        self.k_max = k_max
        self.accel_device = jax.devices()[0]
        self.host_device = _host_device()
        self._segments = [tuple(jax.jit(f) for f in m.segments) for m in self.models]
        # Per model: each segment's params committed to the device that runs
        # it under ``self.plan``.  Requests snapshot the tuple at submit, so
        # a plan switch never pulls params from under an in-flight request.
        self._placed: list[tuple | None] = [None] * len(self.models)
        self._plan_lock = threading.Lock()
        self._tpu = _TpuWorker(self)
        self._pools: list[ThreadPoolExecutor | None] = [None] * len(models)
        self._completed: "queue.Queue[CompletedRequest]" = queue.Queue()
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self._drained = threading.Event()
        self._drained.set()
        self.set_plan(plan)
        self._tpu.start()

    # -- configuration -------------------------------------------------------
    def _device(self, segment: int, partition: int) -> jax.Device:
        return self.accel_device if segment < partition else self.host_device

    def set_plan(self, plan: Plan) -> None:
        if len(plan.partition) != len(self.models):
            raise ValueError("plan size mismatch")
        if sum(plan.cores) > self.k_max:
            raise ValueError("plan exceeds K_max")
        with self._plan_lock:
            for i, m in enumerate(self.models):
                prev = self._placed[i]
                old_p = self.plan.partition[i] if prev is not None else None
                p = plan.partition[i]
                self._placed[i] = tuple(
                    prev[s]
                    if prev is not None
                    and self._device(s, old_p) == self._device(s, p)
                    else jax.device_put(m.params[s], self._device(s, p))
                    for s in range(m.num_partition_points)
                )
            self.plan = plan
            for i, k in enumerate(plan.cores):
                old = self._pools[i]
                if old is not None:
                    old.shutdown(wait=False)
                self._pools[i] = (
                    ThreadPoolExecutor(max_workers=k, thread_name_prefix=f"cpu-{i}")
                    if k > 0
                    else None
                )

    # -- request path ----------------------------------------------------------
    def submit(self, model_idx: int, x: Any) -> None:
        with self._inflight_lock:
            self._inflight += 1
            self._drained.clear()
        submit_t = time.perf_counter()
        with self._plan_lock:
            p = self.plan.partition[model_idx]
            params = self._placed[model_idx]
        if p > 0:
            self._tpu.inbox.put((model_idx, x, p, params, submit_t))
        else:
            try:
                self._dispatch_suffix(model_idx, x, 0, params, submit_t, None)
            except BaseException as exc:
                # The synchronous dispatch path (zero-core misconfiguration,
                # pool rejection) must not leak the in-flight slot it just
                # claimed: record the failure so drain() terminates, then
                # surface it to the submitter.
                self._finish(model_idx, None, submit_t, error=exc)
                raise

    def _run_prefix(
        self, model_idx: int, x: Any, p: int, params: tuple, submit_t: float
    ) -> None:
        # Any failure here (a segment raising, a missing suffix pool) would
        # otherwise die inside the TPU worker thread with the in-flight count
        # still held, hanging every future drain().
        try:
            segs = self._segments[model_idx]
            x = jax.device_put(x, self.accel_device)
            for seg, w in zip(segs[:p], params[:p]):
                x = seg(w, x)
            x = jax.block_until_ready(x)
            if p < len(segs):
                self._dispatch_suffix(model_idx, x, p, params, submit_t, x.device)
            else:
                self._finish(model_idx, x, submit_t, prefix_device=x.device)
        except BaseException as exc:
            self._finish(model_idx, None, submit_t, error=exc)

    def _dispatch_suffix(
        self,
        model_idx: int,
        x: Any,
        p: int,
        params: tuple,
        submit_t: float,
        prefix_device: jax.Device | None,
    ) -> None:
        pool = self._pools[model_idx]
        if pool is None:
            raise RuntimeError(
                f"model {model_idx} has a CPU suffix but zero cores allocated"
            )

        def work() -> None:
            # Same containment as _run_prefix: a suffix failure becomes an
            # errored completion record, never a silently swallowed pool
            # exception plus a leaked in-flight slot.
            try:
                y = jax.device_put(x, self.host_device)
                for seg, w in zip(self._segments[model_idx][p:], params[p:]):
                    y = seg(w, y)
                y = jax.block_until_ready(y)
            except BaseException as exc:
                self._finish(model_idx, None, submit_t, error=exc)
            else:
                self._finish(model_idx, y, submit_t, prefix_device=prefix_device)

        pool.submit(work)

    def _finish(
        self,
        model_idx: int,
        out: Any,
        submit_t: float,
        error: BaseException | None = None,
        prefix_device: jax.Device | None = None,
    ) -> None:
        self._completed.put(
            CompletedRequest(
                model_idx=model_idx,
                submit_time=submit_t,
                done_time=time.perf_counter(),
                output=out,
                error=error,
                prefix_device=prefix_device,
            )
        )
        with self._inflight_lock:
            self._inflight -= 1
            if self._inflight == 0:
                self._drained.set()

    # -- collection ------------------------------------------------------------
    def drain(self, timeout: float = 60.0) -> list[CompletedRequest]:
        if not self._drained.wait(timeout):
            raise TimeoutError("engine did not drain in time")
        out = []
        while True:
            try:
                out.append(self._completed.get_nowait())
            except queue.Empty:
                return out

    def shutdown(self) -> None:
        self._tpu.inbox.put(None)
        for pool in self._pools:
            if pool is not None:
                pool.shutdown(wait=True)
