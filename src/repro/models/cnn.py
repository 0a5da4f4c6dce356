"""Partitionable convolutional model families (the paper's Table II models).

These are real, runnable JAX conv nets used by the serving-engine
integration path and examples: each model is a chain of *stages* (the
paper's partition points) so the SwapLess planner can split them between
the accelerator worker and CPU pools.  Channel widths are chosen per family
so stage weight footprints follow the back-loaded distribution used by the
synthetic profiles.  Weights and inputs are host NumPy arrays drawn from a
seed; the serving engine commits each stage's weights to its device.
(Latency *validation* uses the calibrated profiles + DES; these nets prove
the execution plumbing with real tensors.)
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.engine import ExecutableModel, SegmentFn


@dataclasses.dataclass(frozen=True)
class CNNSpec:
    name: str
    stage_channels: tuple[int, ...]   # output channels per stage
    in_size: int = 64                 # input spatial resolution
    in_channels: int = 3
    kernel: int = 3


def _conv(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def init_cnn(spec: CNNSpec, seed: int) -> list[dict]:
    """One host (NumPy float32) params dict per stage: conv + pointwise conv."""
    rng = np.random.default_rng(seed)
    params = []
    c_in = spec.in_channels
    for c_out in spec.stage_channels:
        fan = spec.kernel * spec.kernel * c_in
        params.append(
            {
                "conv": rng.standard_normal(
                    (spec.kernel, spec.kernel, c_in, c_out), dtype=np.float32
                ) / np.float32(np.sqrt(fan)),
                "pw": rng.standard_normal(
                    (1, 1, c_out, c_out), dtype=np.float32
                ) / np.float32(np.sqrt(c_out)),
            }
        )
        c_in = c_out
    return params


def stage_fn(downsample: bool) -> SegmentFn:
    def fn(p: dict, x: jax.Array) -> jax.Array:
        y = jax.nn.relu(_conv(x, p["conv"], stride=2 if downsample else 1))
        return jax.nn.relu(_conv(y, p["pw"]))
    return fn


def build_executable(spec: CNNSpec, seed: int = 0) -> ExecutableModel:
    params = init_cnn(spec, seed)

    def make_input(seed2: int) -> np.ndarray:
        return np.random.default_rng(seed2).standard_normal(
            (1, spec.in_size, spec.in_size, spec.in_channels), dtype=np.float32
        )

    return ExecutableModel(
        name=spec.name,
        segments=tuple(stage_fn(i % 2 == 0) for i in range(len(params))),
        params=tuple(params),
        make_input=make_input,
    )


def reference(model: ExecutableModel, x: np.ndarray) -> np.ndarray:
    """Plain float32 forward pass: the un-jitted segment chain on the CPU
    device at ``Precision.HIGHEST``."""
    with jax.default_device(jax.devices("cpu")[0]), \
            jax.default_matmul_precision("highest"):
        y = jnp.asarray(x)
        for fn, p in zip(model.segments, model.params):
            y = fn(p, y)
        return np.asarray(y)


def relative_error(y: np.ndarray, ref: np.ndarray) -> float:
    """max|y - ref| / max|ref|; infinite when the shapes differ."""
    y = np.asarray(y)
    if y.shape != ref.shape:
        return float("inf")
    return float(np.abs(y - ref).max() / np.abs(ref).max())


def match_references(
    outputs: list[np.ndarray], refs: list[np.ndarray], copies: int
) -> list[float]:
    """Errors of ``outputs`` against ``refs`` when each input was served
    ``copies`` times and outputs arrive in any order.

    Each output is matched to its nearest reference, and every reference
    must be matched exactly ``copies`` times, so an engine that returns one
    request's output for another fails.  Raises ``ValueError`` otherwise.
    """
    best = []
    for out in outputs:
        errs = [relative_error(out, r) for r in refs]
        j = int(np.argmin(errs))
        best.append((j, errs[j]))
    counts = np.bincount([j for j, _ in best], minlength=len(refs))
    if len(outputs) != copies * len(refs) or not np.all(counts == copies):
        raise ValueError(
            f"outputs match references {counts.tolist()} times, "
            f"expected {copies} each"
        )
    return [e for _, e in best]


# Reduced-scale counterparts of the paper's models (stage count == Table II
# partition points; widths grow with depth like the real families).
PAPER_CNN_SPECS: dict[str, CNNSpec] = {
    "squeezenet": CNNSpec("squeezenet", (16, 32)),
    "mobilenetv2": CNNSpec("mobilenetv2", (8, 16, 24, 32, 48)),
    "efficientnet": CNNSpec("efficientnet", (8, 16, 24, 32, 48, 64)),
    "mnasnet": CNNSpec("mnasnet", (8, 16, 16, 24, 32, 48, 64)),
    "gpunet": CNNSpec("gpunet", (16, 32, 48, 64, 96)),
    "densenet201": CNNSpec("densenet201", (16, 24, 32, 48, 64, 96, 128)),
    "resnet50v2": CNNSpec("resnet50v2", (16, 24, 32, 48, 64, 96, 128, 160)),
    "xception": CNNSpec("xception", (8, 16, 24, 32, 48, 64, 96, 128, 160, 192, 224)),
    "inceptionv4": CNNSpec("inceptionv4", (8, 16, 24, 32, 48, 64, 96, 128, 160, 192, 256)),
}
