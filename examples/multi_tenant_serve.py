"""End-to-end driver: multi-tenant collaborative serving with batched
requests through the real execution engine.

Three co-located CNNs (combined footprint >> 8 MB SRAM) are planned by
SwapLess, then actual JAX inference requests flow through the global
accelerator worker + per-model CPU pools.  The analytic model, the DES, and
the real engine all run on the same plan.

    PYTHONPATH=src python examples/multi_tenant_serve.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.configs.paper_models import paper_profile
from repro.core import latency
from repro.core.allocator import edge_tpu_compiler_plan, swapless_plan
from repro.core.planner import TenantSpec
from repro.hw.specs import EDGE_TPU_PLATFORM
from repro.launch.serve import report_execution
from repro.models.cnn import PAPER_CNN_SPECS, build_executable
from repro.serving.engine import ServingEngine
from repro.serving.simulator import simulate
from repro.serving.workload import poisson_trace

NAMES = ["densenet201", "resnet50v2", "gpunet"]
RATES = [1.2, 1.2, 2.0]
K_MAX = 4


def main() -> None:
    hw = EDGE_TPU_PLATFORM
    tenants = [TenantSpec(paper_profile(n), r) for n, r in zip(NAMES, RATES)]

    plan = swapless_plan(tenants, hw, K_MAX)
    base = edge_tpu_compiler_plan(tenants)
    pred = latency.predict(tenants, plan, hw)
    print("plan:", dict(zip(NAMES, zip(plan.partition, plan.cores))))
    print("alphas:", [f"{a:.2f}" for a in pred.alphas])

    reqs = poisson_trace(RATES, duration=1500.0, seed=1)
    sim = simulate(tenants, plan, hw, reqs)
    simb = simulate(tenants, base, hw, reqs)
    print(
        f"DES mean latency: swapless {sim.overall_mean()*1e3:.1f} ms vs "
        f"compiler {simb.overall_mean()*1e3:.1f} ms "
        f"(-{100*(1 - sim.overall_mean()/simb.overall_mean()):.1f}%)"
    )

    # Batched requests through the real engine.
    models = [build_executable(PAPER_CNN_SPECS[n], seed=i) for i, n in enumerate(NAMES)]
    eng = ServingEngine(models, plan, k_max=K_MAX)
    try:
        n_req = 8
        for i, m in enumerate(models):
            for s in range(n_req):
                eng.submit(i, m.make_input(s))
        done = eng.drain(timeout=180.0)
    finally:
        eng.shutdown()
    report_execution(done, NAMES)  # exits nonzero on any errored record
    for i, n in enumerate(NAMES):
        outs = [c.output for c in done if c.model_idx == i]
        ok = all(np.isfinite(np.asarray(o)).all() for o in outs)
        print(f"  {n:<14} outputs_finite={ok}")


if __name__ == "__main__":
    main()
