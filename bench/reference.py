"""Plain reference of the configurations' tenants, and its control.

A tenant is a chain of stages.  Stage ``i`` is ``relu(conv_kxk)`` with the
stride ``stride_cycle[i % len(stride_cycle)]`` and SAME padding, then
``relu(conv_1x1)``, in NHWC activations and HWIO weights, as the
configuration file states.  This module is written from that statement
alone and imports nothing of the program.  The reference computes in
float32 at ``Precision.HIGHEST`` on the host CPU.

``bfloat16`` and ``fp8`` are the control: the same chain with every
convolution's operands rounded to that type's significand (8 and 4
significant bits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Significant bits kept by the control: bfloat16 has 7 stored mantissa
# bits, float8 e4m3 has 3, each with the implicit leading one.
SIGNIFICANT_BITS = {"float32": None, "bfloat16": 8, "fp8": 4}
PRECISIONS = tuple(SIGNIFICANT_BITS)


def round_significand(a, bits: int):
    """``a`` rounded to ``bits`` significant bits, half to even, with the
    exponent range left as it is: the same arithmetic on every backend."""
    m, e = jnp.frexp(a)
    scale = float(2 ** bits)
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


def _conv(x, w, stride: int, precision=None):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def stage(params: dict, x, *, stride: int, precision: str = "float32"):
    """One stage on ``params`` (``{"conv": HWIO, "pw": 11IO}``)."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")
    bits = SIGNIFICANT_BITS[precision]

    def rnd(a):
        return a if bits is None else round_significand(a, bits)

    hi = lax.Precision.HIGHEST
    y = jax.nn.relu(_conv(rnd(x), rnd(params["conv"]), stride, hi))
    return jax.nn.relu(_conv(rnd(y), rnd(params["pw"]), 1, hi))


def strides(config: dict, n_stages: int) -> list[int]:
    cycle = config["stride_cycle"]
    return [int(cycle[i % len(cycle)]) for i in range(n_stages)]


@functools.partial(jax.jit, static_argnames=("strides", "precision"))
def forward(params: list, x, *, strides: tuple[int, ...], precision: str = "float32"):
    """The whole chain on ``x`` (a batch of inputs, NHWC)."""
    for p, s in zip(params, strides):
        x = stage(p, x, stride=s, precision=precision)
    return x


def references(config: dict, params: list[list], pools: list[np.ndarray],
               block: int = 8) -> list[np.ndarray]:
    """Reference outputs of each tenant's input pool, on the host CPU.

    ``params[m]`` is tenant ``m``'s list of stage dicts and ``pools[m]``
    its inputs stacked on the first axis (each a batch of one).  Computed
    ``block`` inputs at a time.
    """
    cpu = jax.devices("cpu")[0]
    out = []
    with jax.default_device(cpu):
        for p, pool in zip(params, pools):
            p = jax.device_put(p, cpu)
            st = tuple(strides(config, len(p)))
            rows = [np.asarray(forward(p, jnp.asarray(pool[i:i + block, 0]),
                                       strides=st))
                    for i in range(0, len(pool), block)]
            out.append(np.concatenate(rows)[:, None])
    return out


def relative_error(y, ref: np.ndarray) -> float:
    """``max|y - ref| / max|ref|``; infinite when the shapes differ or
    ``y`` is not finite.  Against an all-zero reference it is 0 where ``y``
    is all zero too, and infinite otherwise."""
    y = np.asarray(y, dtype=np.float32)
    if y.shape != ref.shape or not np.all(np.isfinite(y)):
        return float("inf")
    diff, scale = np.abs(y - ref).max(), np.abs(ref).max()
    if scale == 0:
        return 0.0 if diff == 0 else float("inf")
    return float(diff / scale)
