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

Tracing.  Every completion record carries its request's phase stamps on
the host clock (``time.perf_counter``, as ``submit_time``), always on.
The engine also writes ``jax.profiler.TraceAnnotation`` spans, which cost
about a microsecond each while no profiler runs and land on the device
trace's clock while one does.  Each request span carries ``req`` (the
record's ``req_id``) and ``tenant`` as arguments:

- ``engine.worker_wait``: from submit until the accelerator worker takes
  the request from its inbox, and ``engine.pool_wait``: from the handoff
  until a host pool thread starts the suffix.  Each is opened by one
  thread and closed by the next, so the trace puts it on the closing
  thread.
- ``engine.prefix`` on the worker, with children ``engine.h2d`` (the
  input's ``device_put``), ``engine.launch`` (the stage dispatch loop)
  and ``engine.sync`` (``block_until_ready``).
- ``engine.suffix`` on a pool thread, with children ``engine.cut`` (the
  ``device_put`` to the host), ``engine.launch`` and ``engine.sync``.

Each stage program is jitted as ``<model>_stage<s>``, so the device's
modules in a trace say which tenant and stage they run.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Sequence

import jax
from jax.profiler import TraceAnnotation

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
    # Unique, increasing in submit order; the ``req`` of the request's spans.
    req_id: int = -1
    # Phase stamps, on the clock of ``submit_time``: the worker takes the
    # request from its inbox; its prefix is ready; a host pool thread starts
    # the suffix.  ``nan`` where the phase does not exist (no prefix at
    # partition 0, no suffix at partition P) or was not reached before an
    # error.  The phases between consecutive stamps add up to ``latency``.
    prefix_start: float = math.nan
    prefix_end: float = math.nan
    suffix_start: float = math.nan
    # Bytes of the activation handed from the prefix to the suffix; 0 where
    # the plan has no cut.
    cut_bytes: int = 0

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


def _stage_program(fn: SegmentFn, name: str):
    """``fn`` jitted as a program named ``name`` (``jit_<name>`` on the
    device), whatever ``fn`` is: a closure, a ``functools.partial``."""

    def stage(params: Any, x: Any) -> Any:
        return fn(params, x)

    stage.__name__ = stage.__qualname__ = name
    return jax.jit(stage)


def _span_args(rec: CompletedRequest) -> dict:
    return {"req": rec.req_id, "tenant": rec.model_idx}


def _open_span(name: str, rec: CompletedRequest) -> TraceAnnotation:
    """A request's span entered here and left, with ``__exit__``, by the
    thread that takes the request next: its wait in a queue."""
    span = TraceAnnotation(name, **_span_args(rec))
    span.__enter__()
    return span


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
            rec, x, p, params, wait = item
            rec.prefix_start = time.perf_counter()
            wait.__exit__(None, None, None)
            self.engine._run_prefix(rec, x, p, params)


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
        self._segments = [
            tuple(
                _stage_program(f, f"{m.name}_stage{s}")
                for s, f in enumerate(m.segments)
            )
            for m in self.models
        ]
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
        self._req_ids = itertools.count()
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
        rec = CompletedRequest(
            model_idx, time.perf_counter(), math.nan, None, req_id=next(self._req_ids)
        )
        with self._plan_lock:
            p = self.plan.partition[model_idx]
            params = self._placed[model_idx]
        if p > 0:
            wait = _open_span("engine.worker_wait", rec)
            self._tpu.inbox.put((rec, x, p, params, wait))
        else:
            try:
                self._dispatch_suffix(rec, x, 0, params)
            except BaseException as exc:
                # The synchronous dispatch path (zero-core misconfiguration,
                # pool rejection) must not leak the in-flight slot it just
                # claimed: record the failure so drain() terminates, then
                # surface it to the submitter.
                self._finish(rec, None, error=exc)
                raise

    def _run_prefix(
        self, rec: CompletedRequest, x: Any, p: int, params: tuple
    ) -> None:
        # Any failure here (a segment raising, a missing suffix pool) would
        # otherwise die inside the TPU worker thread with the in-flight count
        # still held, hanging every future drain().
        args = _span_args(rec)
        with TraceAnnotation("engine.prefix", **args):
            try:
                segs = self._segments[rec.model_idx]
                with TraceAnnotation("engine.h2d", **args):
                    x = jax.device_put(x, self.accel_device)
                with TraceAnnotation("engine.launch", **args):
                    for seg, w in zip(segs[:p], params[:p]):
                        x = seg(w, x)
                with TraceAnnotation("engine.sync", **args):
                    x = jax.block_until_ready(x)
                rec.prefix_end = time.perf_counter()
                rec.prefix_device = x.device
                if p < len(segs):
                    self._dispatch_suffix(rec, x, p, params)
                else:
                    self._finish(rec, x)
            except BaseException as exc:
                self._finish(rec, None, error=exc)

    def _dispatch_suffix(
        self, rec: CompletedRequest, x: Any, p: int, params: tuple
    ) -> None:
        pool = self._pools[rec.model_idx]
        if pool is None:
            raise RuntimeError(
                f"model {rec.model_idx} has a CPU suffix but zero cores allocated"
            )
        if p > 0:
            rec.cut_bytes = x.nbytes
        args = _span_args(rec)
        wait = _open_span("engine.pool_wait", rec)

        def work() -> None:
            rec.suffix_start = time.perf_counter()
            wait.__exit__(None, None, None)
            # Same containment as _run_prefix: a suffix failure becomes an
            # errored completion record, never a silently swallowed pool
            # exception plus a leaked in-flight slot.
            with TraceAnnotation("engine.suffix", **args):
                try:
                    with TraceAnnotation("engine.cut", **args):
                        y = jax.device_put(x, self.host_device)
                    with TraceAnnotation("engine.launch", **args):
                        segs = self._segments[rec.model_idx]
                        for seg, w in zip(segs[p:], params[p:]):
                            y = seg(w, y)
                    with TraceAnnotation("engine.sync", **args):
                        y = jax.block_until_ready(y)
                except BaseException as exc:
                    self._finish(rec, None, error=exc)
                else:
                    self._finish(rec, y)

        try:
            pool.submit(work)
        except BaseException:
            wait.__exit__(None, None, None)
            raise

    def _finish(
        self, rec: CompletedRequest, out: Any, error: BaseException | None = None
    ) -> None:
        rec.done_time = time.perf_counter()
        rec.output = out
        rec.error = error
        self._completed.put(rec)
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
