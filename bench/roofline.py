"""Chip peaks and the operations and bytes of each stage, from the
configuration's shapes alone."""
from __future__ import annotations

import dataclasses
import math

# Published peaks per chip, keyed by JAX's ``device_kind``.  TPU v5e:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
# A float32 convolution at JAX's DEFAULT precision is one bf16 pass on
# this chip, so the bf16 peak is its compute roof.
PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}

F32_BYTES = 4


def peaks(device_kind: str) -> dict[str, float]:
    """The peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class StageCost:
    flops: float
    min_bytes: float      # stage input, weights and output, float32
    out_bytes: float      # the output activation alone

    def roofline_s(self, peak: dict[str, float]) -> float:
        """Least time the chip could take: the larger of the two bounds."""
        return max(self.flops / peak["flops"],
                   self.min_bytes / peak["hbm_bytes_per_s"])


def stage_costs(config: dict, tenant: dict) -> list[StageCost]:
    """One ``StageCost`` per stage of ``tenant``.

    A stage is a ``k x k`` convolution (stride from the configuration's
    ``stride_cycle``, SAME padding) and a pointwise convolution, both with
    ReLU, on a batch of one.  A multiply-add counts two operations; ReLU
    and padding count none.  The intermediate between the two convolutions
    is not counted in the bytes, since a fused stage need not write it.
    """
    k, strides = int(config["kernel"]), config["stride_cycle"]
    h, c_in = int(tenant["input_size"]), int(config["in_channels"])
    out = []
    for i, c_out in enumerate(tenant["stage_channels"]):
        ho = math.ceil(h / int(strides[i % len(strides)]))
        weights = k * k * c_in * c_out + c_out * c_out
        flops = 2.0 * ho * ho * weights
        act_in, act_out = h * h * c_in, ho * ho * c_out
        out.append(StageCost(flops=flops,
                             min_bytes=F32_BYTES * (act_in + weights + act_out),
                             out_bytes=F32_BYTES * act_out))
        h, c_in = ho, c_out
    return out
