"""The trace reduction on a constructed trace with overlapping events."""
import numpy as np
import pytest

from bench import tracing
from bench.harness import stage_executions
from repro.core.planner import Plan


def events():
    # Window [100, 200) ns.  Device ops overlap: busy is [90,130) u [150,170)
    # u [190,210), clipped to the window: 30 + 20 + 10 = 60 ns.
    ops = [(90, 120, "%fusion.1 = bf16[8]{0}"), (110, 130, "%copy.2 = f32[8]{0}"),
           (150, 160, "%fusion.1 = bf16[8]{0}"), (155, 170, "%fusion.1 = bf16[8]{0}"),
           (190, 210, "%fusion.1 = bf16[8]{0}")]
    host = {"python#0": [(125, 155, "PjitFunction(fn)")],
            "tf_XLAPjRtCpuClient/1#1": [(160, 185, "ThunkExecutor::Execute"),
                                        (170, 180, "conv")],
            "tf_XLAPjRtCpuClient/2#2": [(50, 120, "ThunkExecutor::Execute")]}
    return tracing.Events(ops=ops, modules=[], host=host, window=(100, 200))


def test_union_and_gaps():
    m = tracing.merged(events().ops, 100, 200)
    np.testing.assert_array_equal(m, [[100, 130], [150, 170], [190, 200]])
    assert tracing.gaps(m, 100, 200) == [(130, 150), (170, 190)]
    assert tracing.gaps(np.empty((0, 2)), 0, 5) == [(0, 5)]


def test_reduce_busy_idle_and_breakdown():
    r = tracing.reduce(events())
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(60e-9)
    assert r.idle_frac == pytest.approx(0.4)
    # Op time inside the window, summed by op name up to its layout.
    assert r.device_ops[0] == ["%fusion.1 = bf16[8]", pytest.approx(55e-9)]
    assert r.device_ops[1] == ["%copy.2 = f32[8]", pytest.approx(20e-9)]
    # Two 20 ns gaps, labelled by the host event overlapping each the most.
    assert [g[0] for g in r.idle_gaps] == ["python: PjitFunction(fn)",
                                          "tf_XLAPjRtCpuClient: ThunkExecutor::Execute"]
    assert [g[1] for g in r.idle_gaps] == [pytest.approx(20e-9)] * 2


def test_host_xla_cpu_busy():
    # Thread 1: [160, 185) inside the window (the nested event adds
    # nothing); thread 2: [100, 120).
    assert tracing.host_xla_cpu_busy_s(events()) == pytest.approx(45e-9)


def test_stage_executions_follow_submit_order():
    plan = Plan(partition=(2, 0, 1), cores=(1, 1, 1))
    order = np.array([0, 2, 0])          # tenant 1 runs no prefix
    mods = [(100, 110, "a"), (110, 120, "b"), (120, 130, "c"),
            (130, 140, "a"), (250, 260, "b")]
    ev = tracing.Events(ops=[], modules=mods, host={}, window=(100, 200))
    assert stage_executions(ev, order, plan) == [
        (0, 0, 100, 110), (0, 1, 110, 120), (2, 0, 120, 130), (0, 0, 130, 140)]
    # A program that does not fit the attribution: nothing to read.
    mods[3] = (130, 140, "z")
    assert stage_executions(ev, order, plan) is None
