"""Operations and bytes of a stage against a hand count, and the peaks
table."""
import pytest

from bench import roofline

CONFIG = {"kernel": 3, "in_channels": 3, "stride_cycle": [2, 1]}


def test_stride_two_and_stride_one_stage_by_hand():
    tenant = {"input_size": 224, "stage_channels": [16, 24]}
    s0, s1 = roofline.stage_costs(CONFIG, tenant)
    # Stage 0: 3x3 stride 2, 224 -> 112, 3 -> 16 channels, then 1x1 16 -> 16.
    assert s0.flops == 2 * 112 * 112 * (9 * 3 * 16) + 2 * 112 * 112 * (16 * 16)
    assert s0.min_bytes == 4 * (224 * 224 * 3 + 9 * 3 * 16 + 16 * 16 + 112 * 112 * 16)
    assert s0.out_bytes == 4 * 112 * 112 * 16
    # Stage 1: 3x3 stride 1 at 112, 16 -> 24 channels, then 1x1 24 -> 24.
    assert s1.flops == 2 * 112 * 112 * (9 * 16 * 24) + 2 * 112 * 112 * (24 * 24)
    assert s1.min_bytes == 4 * (112 * 112 * 16 + 9 * 16 * 24 + 24 * 24 + 112 * 112 * 24)


def test_odd_size_rounds_up_like_same_padding():
    (s0,) = roofline.stage_costs(CONFIG, {"input_size": 299, "stage_channels": [8]})
    assert s0.out_bytes == 4 * 150 * 150 * 8


def test_roofline_time_is_the_larger_bound():
    peak = roofline.peaks("TPU v5 lite")
    c = roofline.StageCost(flops=197e12, min_bytes=819e9 / 2, out_bytes=0)
    assert c.roofline_s(peak) == pytest.approx(1.0)
    c = roofline.StageCost(flops=1.0, min_bytes=819e9, out_bytes=0)
    assert c.roofline_s(peak) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
