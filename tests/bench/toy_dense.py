"""A model family for the harness's tests, copied into a benchmark tree as
``bench/families/toy_dense.py``: each tenant a chain of ``relu(x @ W)``
segments on inputs of shape ``(length, width)``, whose lengths cycle
through the tenant's ``lengths``.

Weights and inputs are drawn on the host from the seed; the plan is the
configuration's own; the reference is NumPy in float64.  ``bfloat16`` is
the control: every segment's operands rounded to bfloat16.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench.roofline import F32_BYTES, StageCost
from repro.core.planner import Plan
from repro.serving.engine import ExecutableModel

CONTROLS = ("bfloat16",)


def _widths(tenant):
    return list(zip(tenant["widths"][:-1], tenant["widths"][1:]))


def make_data(config, pool_size, seed, device):
    rng = np.random.default_rng(seed)
    params = [[{"w": (rng.standard_normal((a, b)) / np.sqrt(a)).astype(np.float32)}
               for a, b in _widths(t)] for t in config["tenants"]]
    pools = [[rng.standard_normal((t["lengths"][i % len(t["lengths"])],
                                   t["widths"][0])).astype(np.float32)
              for i in range(pool_size)] for t in config["tenants"]]
    return params, pools


def _segment(p, x):
    return jax.nn.relu(x @ p["w"])


def _segment_bf16(p, x):
    y = x.astype(jnp.bfloat16) @ p["w"].astype(jnp.bfloat16)
    return jax.nn.relu(y.astype(jnp.float32))


def build_models(config, params, control):
    seg = _segment if control is None else _segment_bf16
    return [ExecutableModel(t["name"], tuple(seg for _ in p), tuple(p),
                            lambda s, t=t: np.zeros((t["lengths"][0], t["widths"][0]),
                                                    np.float32))
            for t, p in zip(config["tenants"], params)]


def make_plan(config, rates):
    return Plan(partition=tuple(t["partition"] for t in config["tenants"]),
                cores=tuple(t["cores"] for t in config["tenants"]))


def references(config, host_params, pools):
    out = []
    for p, pool in zip(host_params, pools):
        refs = []
        for x in pool:
            y = np.asarray(x, np.float64)
            for seg in p:
                y = np.maximum(y @ np.asarray(seg["w"], np.float64), 0.0)
            refs.append(y.astype(np.float32))
        out.append(refs)
    return out


def stage_costs(config, tenant):
    """Per stage at the tenant's mean input length."""
    n = np.mean(tenant["lengths"])
    return [StageCost(flops=2.0 * n * a * b,
                      min_bytes=F32_BYTES * (n * a + a * b + n * b),
                      out_bytes=F32_BYTES * n * b)
            for a, b in _widths(tenant)]
