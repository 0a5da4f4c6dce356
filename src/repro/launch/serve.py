"""Multi-tenant serving launcher -- the paper's technique as a first-class
feature of the framework.

Co-locates several models behind one accelerator worker with bounded fast
memory.  The SwapLess planner (analytic queueing model + hill-climbing) picks
each model's accelerator prefix / host suffix split and host core allocation;
requests then flow through the real execution engine (JAX compute) while the
calibrated platform model predicts the latency the same plan would see on the
edge testbed.

    PYTHONPATH=src python -m repro.launch.serve \
        --models inceptionv4,mnasnet --rates 2.0,5.0 --duration 30
"""
from __future__ import annotations

import argparse
from typing import Sequence

import numpy as np

from repro.configs.paper_models import paper_profile
from repro.core import latency
from repro.core.allocator import (
    edge_tpu_compiler_plan,
    swapless_plan,
)
from repro.core.planner import TenantSpec
from repro.hw.specs import EDGE_TPU_PLATFORM
from repro.launch.compile_cache import enable_compile_cache
from repro.models.cnn import PAPER_CNN_SPECS, build_executable
from repro.serving.engine import CompletedRequest, ServingEngine
from repro.serving.simulator import simulate
from repro.serving.workload import poisson_trace


def report_execution(
    done: Sequence[CompletedRequest], names: Sequence[str]
) -> None:
    """Print per-model latency over the successful records and exit nonzero
    when any record errored; an errored record never enters a mean."""
    ok = [c for c in done if c.ok]
    failed = [c for c in done if not c.ok]
    print(f"real execution: {len(ok)}/{len(done)} requests ok")
    for i, name in enumerate(names):
        ls = np.array([c.latency for c in ok if c.model_idx == i])
        if ls.size:
            print(
                f"  {name:<14} n={ls.size} mean={ls.mean()*1e3:.2f}ms "
                f"p95={np.percentile(ls, 95)*1e3:.2f}ms"
            )
        else:
            print(f"  {name:<14} n=0")
    for c in failed:
        print(f"  {names[c.model_idx]:<14} error: {c.error!r}")
    if failed:
        raise SystemExit(f"{len(failed)} of {len(done)} requests failed")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="inceptionv4,mnasnet")
    ap.add_argument("--rates", default="2.0,5.0")
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--requests", type=int, default=20,
                    help="real-execution requests per model")
    ap.add_argument("--k-max", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    names = args.models.split(",")
    rates = [float(r) for r in args.rates.split(",")]
    hw = EDGE_TPU_PLATFORM
    tenants = [TenantSpec(paper_profile(n), r) for n, r in zip(names, rates)]

    # --- plan ---------------------------------------------------------------
    plan = swapless_plan(tenants, hw, args.k_max)
    baseline = edge_tpu_compiler_plan(tenants)
    pred = latency.predict(tenants, plan, hw)
    pred_base = latency.predict(tenants, baseline, hw)
    print("SwapLess plan:")
    for t, p, k, a in zip(tenants, plan.partition, plan.cores, pred.alphas):
        P = t.profile.num_partition_points
        print(
            f"  {t.profile.name:<14} prefix={p}/{P} cores={k} alpha={a:.2f} "
            f"predicted={pred.latencies[names.index(t.profile.name)]*1e3:.1f}ms"
        )
    print(
        f"predicted mean latency: swapless={pred.mean_latency(tenants)*1e3:.1f}ms "
        f"vs compiler={pred_base.mean_latency(tenants)*1e3:.1f}ms"
    )

    # --- DES over the full duration ------------------------------------------
    reqs = poisson_trace(rates, args.duration, seed=0)
    sim = simulate(tenants, plan, hw, reqs)
    sim_base = simulate(tenants, baseline, hw, reqs)
    print(
        f"simulated mean latency ({len(reqs)} requests): "
        f"swapless={sim.overall_mean()*1e3:.1f}ms "
        f"compiler={sim_base.overall_mean()*1e3:.1f}ms "
        f"(-{100*(1-sim.overall_mean()/max(sim_base.overall_mean(),1e-12)):.1f}%)"
    )

    # --- real execution through the engine ------------------------------------
    models = [build_executable(PAPER_CNN_SPECS[n], seed=i) for i, n in enumerate(names)]
    eng = ServingEngine(models, plan, k_max=args.k_max)
    try:
        for i, m in enumerate(models):
            for s in range(args.requests):
                eng.submit(i, m.make_input(s))
        done = eng.drain(timeout=120.0)
    finally:
        eng.shutdown()
    report_execution(done, names)


if __name__ == "__main__":
    main()
