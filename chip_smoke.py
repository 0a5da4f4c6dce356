#!/usr/bin/env python3
"""One-chip smoke run of the served path and the planner kernels.

    python3 chip_smoke.py

Run from the repository root with ``JAX_PLATFORMS`` unset, so that both the
TPU and the host CPU backend exist.  One process; a few minutes.  It needs a
TPU: without one, or on any failed check, it exits nonzero and never falls
back to the CPU.

Phases:

* ``engine`` -- four ``PAPER_CNN_SPECS`` tenants (seeded weights) through
  ``ServingEngine`` under three plans switched live with ``set_plan``: the
  SwapLess plan on the Edge-TPU platform model (cuts inside every tenant),
  the all-accelerator plan and the all-host plan.  Every request must be
  ok, match the plain float32 reference, hold its prefix output on the TPU
  and, below the last partition point, its final output on the CPU.
* ``planner`` -- ``hill_climb`` with the on-device ``JaxPlanEvaluator``
  returns the NumPy search's plan on the ``thrash16`` and ``collab8`` mixes.
* ``replicas`` -- ``JaxStepper.run_trace_replicas`` on the device matches
  the NumPy stepper's per-replica means within the float32 contract.

Each phase runs its work twice and prints the compile seconds of the first
pass (JAX tracing, lowering and backend compile or persistent-cache fetch,
summed over the engine's threads) and the wall seconds of both passes.  The last line
of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

TENANTS = ("densenet201", "resnet50v2", "gpunet", "inceptionv4")
RATE = 2.0           # per-tenant rate the SwapLess plan is made for
K_MAX = 4
N_REQUESTS = 4       # per tenant, plan and pass (two passes)
# max|y - ref| / max|ref| against the float32 HIGHEST reference.  TPU
# float32 convolutions default to one bf16 pass; emulating that on the CPU
# gives 7e-3 to 1.74e-2 over these tenants, a wrong stage 0.4 or more
# (both checked in tests/test_cnn_models.py).
REF_TOL = 5e-2
# Float32 contract of the JAX planner paths (tests/test_jax_sim.py).
MEAN_RTOL = 2e-4
BUSY_RTOL = 1e-4
OBJ_RTOL = 1e-4
N_TRACE = 100_000
N_REPLICAS = 8


class Failed(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise Failed(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling or fetching from
    the persistent cache, summed over threads, plus the cache's hits, from
    JAX's monitoring events."""

    def __init__(self):
        import jax

        self.total = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.total += secs

    def _on_event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def timed_phase(name: str, clock: CompileClock, fn):
    """Run ``fn`` twice: the first call compiles, the second is timed as
    the run.  Returns both results."""
    c0, t0 = clock.total, time.perf_counter()
    first = fn()
    t1 = time.perf_counter()
    comp = clock.total - c0
    second = fn()
    t2 = time.perf_counter()
    print(f"[{name}] compile_s={comp:.3f} first_wall_s={t1 - t0:.3f} "
          f"run_s={t2 - t1:.3f}")
    return first, second


# -- engine ------------------------------------------------------------------
def engine_phase(clock: CompileClock) -> None:
    import numpy as np

    from repro.configs.paper_models import paper_profile
    from repro.core.allocator import swapless_plan
    from repro.core.planner import Plan, TenantSpec
    from repro.hw.specs import EDGE_TPU_PLATFORM
    from repro.models.cnn import (PAPER_CNN_SPECS, build_executable,
                                  match_references, reference)
    from repro.serving.engine import ServingEngine

    models = [build_executable(PAPER_CNN_SPECS[n], seed=i)
              for i, n in enumerate(TENANTS)]
    points = tuple(m.num_partition_points for m in models)
    tenants = [TenantSpec(paper_profile(n), RATE) for n in TENANTS]
    swapless = swapless_plan(tenants, EDGE_TPU_PLATFORM, K_MAX)
    check(all(0 < p < pp for p, pp in zip(swapless.partition, points)),
          f"SwapLess plan {swapless.partition} does not cut inside every "
          f"tenant of {points}")
    plans = {
        "swapless": swapless,
        "all_accel": Plan(points, (0,) * len(models)),
        "all_host": Plan((0,) * len(models), (1,) * len(models)),
    }
    inputs = [[m.make_input(s) for s in range(N_REQUESTS)] for m in models]
    refs = [[reference(m, x) for x in xs] for m, xs in zip(models, inputs)]

    eng = ServingEngine(models, plans["swapless"], k_max=K_MAX)
    try:
        print(f"engine: accel={eng.accel_device} host={eng.host_device}")
        for pname, plan in plans.items():
            eng.set_plan(plan)

            def serve():
                for i in range(len(models)):
                    for s in range(N_REQUESTS):
                        eng.submit(i, inputs[i][s])
                return eng.drain(timeout=600.0)

            done = sum(timed_phase(f"engine/{pname}", clock, serve), [])
            check(len(done) == 2 * len(models) * N_REQUESTS,
                  f"{pname}: {len(done)} records")
            bad = [c for c in done if not c.ok]
            if bad:
                raise Failed(f"{pname}: {len(bad)} errored, first: "
                             f"{bad[0].error!r}")
            for i, m in enumerate(models):
                mine = [c for c in done if c.model_idx == i]
                p = plan.partition[i]
                # Requests of one tenant may complete out of submit order;
                # each input was served once per pass, so each reference
                # must be matched by exactly two outputs.
                try:
                    errs = match_references(
                        [np.asarray(c.output) for c in mine], refs[i], 2)
                except ValueError as exc:
                    raise Failed(f"{pname}/{m.name}: {exc}") from None
                pre = {c.prefix_device.platform if c.prefix_device else None
                       for c in mine}
                fin = {c.output.device.platform for c in mine}
                print(f"  {pname:<9} {m.name:<12} cut={p}/{points[i]} "
                      f"n={len(mine)} ok=True max_rel_err={max(errs):.3e} "
                      f"prefix_on={sorted(map(str, pre))} "
                      f"final_on={sorted(fin)}")
                check(max(errs) <= REF_TOL,
                      f"{pname}/{m.name}: reference error {max(errs):.3e} "
                      f"> {REF_TOL}")
                if p > 0:
                    check(pre == {"tpu"}, f"{pname}/{m.name}: prefix on {pre}")
                if p < points[i]:
                    check(fin == {"cpu"}, f"{pname}/{m.name}: final on {fin}")
    finally:
        eng.shutdown()


# -- planner -----------------------------------------------------------------
def _mix_tenants(name: str):
    import numpy as np

    from benchmarks.sim_throughput import _mixes
    from repro.core.planner import TenantSpec

    ts, _, _ = _mixes()[name]
    rng = np.random.default_rng(1)
    return [TenantSpec(t.profile, float(r))
            for t, r in zip(ts, rng.uniform(0.5, 4.0, len(ts)))]


def planner_phase(clock: CompileClock) -> None:
    from repro.core.allocator import hill_climb
    from repro.core.plan_tables import EvalTables
    from repro.hw.specs import EDGE_TPU_PLATFORM as HW

    for mix in ("thrash16", "collab8"):
        ts = _mix_tenants(mix)
        k_max = max(4, len(ts))
        et = EvalTables.build(ts, HW, k_max)
        p_ref, o_ref = hill_climb(ts, HW, k_max, tables=et, batch=True)

        def climb():
            ev = et.to_jax()
            return ev, hill_climb(ts, HW, k_max, evaluator=ev)

        first, (ev, (p_jax, o_jax)) = timed_phase(f"planner/{mix}", clock, climb)
        check(first[1] == (p_jax, o_jax), f"{mix}: climbs disagree")
        plat = {d.platform for d in ev.pstack.devices()}
        rel = abs(o_jax - o_ref) / abs(o_ref)
        print(f"  {mix}: evaluator on {sorted(plat)} plans_identical="
              f"{p_jax == p_ref} objective_rel_diff={rel:.3e}")
        check(plat == {"tpu"}, f"{mix}: evaluator tables on {plat}")
        check(p_jax == p_ref, f"{mix}: plan {p_jax} != NumPy plan {p_ref}")
        check(rel <= OBJ_RTOL, f"{mix}: objective rel diff {rel:.3e}")


# -- replicas ----------------------------------------------------------------
def replicas_phase(clock: CompileClock) -> None:
    import numpy as np

    from benchmarks.sim_throughput import _mixes
    from repro.hw.specs import EDGE_TPU_PLATFORM as HW
    from repro.serving.simulator import make_backend, simulate
    from repro.serving.workload import Trace

    ts, plan, _ = _mixes()["collab8"]
    profs = [t.profile for t in ts]
    rates = np.asarray([2.4] * 4 + [15.0] * 4)
    rng = np.random.default_rng(21)
    lam = float(rates.sum())
    trace = Trace(
        rng.choice(len(rates), size=N_TRACE, p=rates / lam).astype(np.int64),
        np.cumsum(rng.exponential(1.0 / lam, N_TRACE)),
    )
    scales = np.random.default_rng(22).uniform(
        0.8, 1.25, size=(N_REPLICAS, len(profs)))
    run = lambda: make_backend("jax", profs, plan, HW).run_trace_replicas(
        trace, scales)
    first, stats = timed_phase("replicas", clock, run)
    check(np.array_equal(first.mean_latency, stats.mean_latency),
          "replica runs disagree")

    worst_mean = worst_busy = 0.0
    for r in range(N_REPLICAS):
        ref = simulate(ts, plan, HW,
                       Trace(trace.model_idx, trace.arrival,
                             scales[r][trace.model_idx]),
                       warmup_frac=0.0)
        for m in range(len(profs)):
            check(stats.counts[m] == len(ref.latencies[m]),
                  f"replica {r} model {m}: counts differ")
            worst_mean = max(worst_mean, abs(
                stats.mean_latency[r, m] - ref.mean_latency(m)
            ) / abs(ref.mean_latency(m)))
        check(list(stats.misses) == ref.misses, f"replica {r}: misses differ")
        worst_busy = max(worst_busy,
                         abs(stats.tpu_busy[r] - ref.tpu_busy) / ref.tpu_busy)
    print(f"  {N_TRACE} requests x {N_REPLICAS} replicas: "
          f"max_rel_mean_diff={worst_mean:.3e} (bound {MEAN_RTOL}) "
          f"max_rel_busy_diff={worst_busy:.3e} (bound {BUSY_RTOL})")
    check(worst_mean <= MEAN_RTOL, f"replica means off by {worst_mean:.3e}")
    check(worst_busy <= BUSY_RTOL, f"replica busy off by {worst_busy:.3e}")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found {dev.platform} "
              f"({dev.device_kind}); it does not fall back to the CPU",
              file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository source under {ROOT}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}")
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        engine_phase(clock)
        planner_phase(clock)
        replicas_phase(clock)
    except Failed as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(f"total: compile_s={clock.total:.3f} "
          f"cache_hits={clock.cache_hits} "
          f"wall_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
