"""The conv chain: tenants of ``conv3x3_pointwise`` stages, the family of a
configuration with no ``family`` key.

A tenant is a chain of stages, each ``relu(conv kxk)`` then
``relu(conv 1x1)``, built by the program's ``repro.models.cnn``; its
requests are single NHWC images of one size, and its plan profile is one
of the paper's Coral CNNs.  The plain reference is ``bench/reference.py``
and the costs are ``bench/roofline.stage_costs``.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

from bench import reference, roofline

# The reference at a lower precision, served in the program's place.
CONTROLS = ("bfloat16", "fp8")

references = reference.references
stage_costs = roofline.stage_costs


def stage_shapes(config: dict, tenant: dict) -> list[dict]:
    k, c_in, out = int(config["kernel"]), int(config["in_channels"]), []
    for c in tenant["stage_channels"]:
        out.append({"conv": (k, k, c_in, c), "pw": (1, 1, c, c)})
        c_in = c
    return out


def make_data(config: dict, pool_size: int, seed: int, device):
    """Every tenant's stage weights and input pool, drawn from ``seed`` on
    ``device`` in one jitted call, in float32 as they are served.  Weights
    follow the program's scaling: standard normal over the root of the
    fan-in.  One draw covers all weights and one each tenant's pool, so
    the program stays small to trace and compile."""
    import jax
    import jax.numpy as jnp

    tenants, c_in = config["tenants"], int(config["in_channels"])
    leaves = [(m, name, shp[name])
              for m, t in enumerate(tenants)
              for shp in stage_shapes(config, t) for name in ("conv", "pw")]

    def gen(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        flat = jax.random.normal(jax.random.fold_in(key, 0),
                                 (sum(math.prod(s) for _, _, s in leaves),))
        params, off = [[] for _ in tenants], 0
        for m, name, s in leaves:
            n = math.prod(s)
            fan_in = math.prod(s[:3])
            w = flat[off:off + n].reshape(s) / math.sqrt(fan_in)
            off += n
            if name == "conv":
                params[m].append({"conv": w})
            else:
                params[m][-1]["pw"] = w
        pools = [jax.random.normal(jax.random.fold_in(key, 1 + m),
                                   (pool_size, 1, int(t["input_size"]),
                                    int(t["input_size"]), c_in), jnp.float32)
                 for m, t in enumerate(tenants)]
        return params, pools

    # Seeds may pass 32 bits: the high part is folded in.
    with jax.default_device(device):
        return jax.jit(gen)(np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32))


def build_models(config: dict, params: list, control: str | None):
    """The tenants as the program builds them (``build_executable``), with
    the seed's weights; under ``control`` their stages are the reference's
    at that precision instead."""
    import jax
    from repro.models.cnn import CNNSpec, build_executable

    models = []
    for t, p in zip(config["tenants"], params):
        spec = CNNSpec(t["name"], tuple(t["stage_channels"]),
                       in_size=int(t["input_size"]),
                       in_channels=int(config["in_channels"]),
                       kernel=int(config["kernel"]))
        model = build_executable(spec)
        want = jax.tree.map(np.shape, list(model.params))
        got = jax.tree.map(np.shape, p)
        if want != got:
            raise ValueError(f"{t['name']}: the program's stage weights are "
                             f"{want}, the configuration's {got}")
        segments = model.segments
        if control is not None:
            segments = tuple(
                functools.partial(reference.stage, stride=s, precision=control)
                for s in reference.strides(config, len(p)))
        models.append(dataclasses.replace(model, params=tuple(p),
                                          segments=segments))
    return models


def make_plan(config: dict, rates):
    """The program's SwapLess plan at the offered per-tenant rates."""
    import repro.hw.specs as specs
    from repro.configs.paper_models import paper_profile
    from repro.core.allocator import swapless_plan
    from repro.core.planner import TenantSpec

    platform = getattr(specs, config["planner_platform"])
    tenants = []
    for t, r in zip(config["tenants"], rates):
        prof = paper_profile(t["profile"], platform)
        if prof.num_partition_points != len(t["stage_channels"]):
            raise ValueError(f"{t['name']}: profile {t['profile']!r} has "
                             f"{prof.num_partition_points} partition points, "
                             f"the tenant {len(t['stage_channels'])} stages")
        tenants.append(TenantSpec(prof, float(r)))
    return swapless_plan(tenants, platform, int(config["k_max"]))
