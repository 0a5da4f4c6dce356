"""Production mesh construction (TPU v5e pods).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before calling it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh with ``Auto`` axes: the model code's ``with_sharding_constraint``
    specs are only accepted on Auto axes (``jax.make_mesh`` defaults to
    Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over real local devices (tests/examples)."""
    return auto_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the global batch shards over."""
    if "pod" in mesh.axis_names:
        return ("pod", "data")
    return ("data",)
