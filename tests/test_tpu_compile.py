"""Compiles for a described TPU v5e, with no chip attached.

The only test file that describes the chip: the served path's stage
programs and the planner kernels at their real widths, compiled by the
TPU compiler that ships with jaxlib.  What the chip's compiler would refuse
fails here at no chip time; nothing runs, so nothing here is a result or a
time.  The topology is described inside a fixture (never at import), so
every test worker collects the same tests and only the worker given this
file loads the TPU library.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_models import PAPER_MODEL_NAMES, paper_profile
from repro.core.jax_eval import _objective_kernel
from repro.core.plan_tables import EvalTables
from repro.core.planner import TenantSpec
from repro.hw.specs import EDGE_TPU_PLATFORM
from repro.models.cnn import PAPER_CNN_SPECS, build_executable
from repro.serving.jax_stepper import _grid, _tpu_replicas_kernel

INCEPTION = PAPER_CNN_SPECS["inceptionv4"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sharding),
        tree,
    )


@pytest.mark.parametrize("stage", range(len(INCEPTION.stage_channels)))
def test_inceptionv4_stage_compiles(one_chip, stage):
    model = build_executable(INCEPTION, seed=0)
    x = jax.ShapeDtypeStruct(
        (1, INCEPTION.in_size, INCEPTION.in_size, INCEPTION.in_channels),
        jnp.float32,
    )
    for fn, p in zip(model.segments[:stage], model.params[:stage]):
        x = jax.eval_shape(fn, p, x)
    compiled = (
        jax.jit(model.segments[stage])
        .lower(_sds(model.params[stage], one_chip), _sds(x, one_chip))
        .compile()
    )
    assert compiled.memory_analysis() is not None


def test_replicas_kernel_compiles_at_1m_by_32(one_chip):
    n_req, n_rep, n_models = 1_000_000, 32, 8
    c, l = _grid(n_req)
    col = lambda dt: jax.ShapeDtypeStruct((c * l,), dt, sharding=one_chip)
    args = (
        col(jnp.float32), col(jnp.float32), col(jnp.float32), col(jnp.int32),
        jax.ShapeDtypeStruct((n_rep, n_models), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n_rep,), jnp.float32, sharding=one_chip),
    )
    lowered = _tpu_replicas_kernel.lower(*args, c=c, l=l, n_models=n_models)
    dots = [ln for ln in lowered.as_text().splitlines() if "dot_general" in ln]
    # The per-model delay sums: a DEFAULT float32 dot is one bf16 pass on
    # the chip, outside the ~1e-4 relative contract.
    assert dots and all("HIGHEST" in ln for ln in dots), dots
    mem = lowered.compile().memory_analysis()
    assert mem.temp_size_in_bytes < 16e9


def test_plan_evaluator_kernel_compiles_at_64_tenants(one_chip):
    n = 64
    names = [PAPER_MODEL_NAMES[i % len(PAPER_MODEL_NAMES)] for i in range(n)]
    ts = [TenantSpec(paper_profile(name), 1.0) for name in names]
    ev = EvalTables.build(ts, EDGE_TPU_PLATFORM, n).to_jax()
    frontier = jax.ShapeDtypeStruct((4 * n, n), jnp.int32, sharding=one_chip)
    compiled = _objective_kernel.lower(
        *_sds((ev.pstack, ev.pkstack, ev.rates, ev.svc_tab, ev.tl_tab), one_chip),
        float(ev.et.sram_bytes), frontier, frontier,
        force_alpha_zero=False, batches=False, batch_cap=1, staleness=0.0,
    ).compile()
    assert compiled.memory_analysis() is not None
