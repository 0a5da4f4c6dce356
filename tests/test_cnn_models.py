"""Tests for the partitionable CNN families (paper Table II) and their
integration with the real-execution serving engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from repro.core.planner import Plan
from repro.models import cnn
from repro.models.cnn import (
    PAPER_CNN_SPECS,
    build_executable,
    init_cnn,
    match_references,
    reference,
    relative_error,
)
from repro.serving.engine import ExecutableModel, ServingEngine


def test_specs_match_table_ii_partition_points():
    expected = {
        "squeezenet": 2,
        "mobilenetv2": 5,
        "efficientnet": 6,
        "mnasnet": 7,
        "gpunet": 5,
        "densenet201": 7,
        "resnet50v2": 8,
        "xception": 11,
        "inceptionv4": 11,
    }
    for name, pp in expected.items():
        assert len(PAPER_CNN_SPECS[name].stage_channels) == pp, name


@pytest.mark.parametrize("name", ["mobilenetv2", "squeezenet"])
def test_cnn_forward_shapes(name):
    model = build_executable(PAPER_CNN_SPECS[name], seed=0)
    x = model.make_input(0)
    for seg, p in zip(model.segments, model.params):
        x = seg(p, x)
    x = np.asarray(x)
    assert np.all(np.isfinite(x))
    assert x.shape[-1] == PAPER_CNN_SPECS[name].stage_channels[-1]


def test_partitioned_equals_unpartitioned():
    model = build_executable(PAPER_CNN_SPECS["mobilenetv2"], seed=1)
    x0 = model.make_input(7)
    full = reference(model, x0)
    segs = [jax.jit(f) for f in model.segments]
    for p in range(len(model.segments) + 1):
        y = x0
        for seg, w in zip(segs[:p], model.params[:p]):
            y = seg(w, y)
        for seg, w in zip(segs[p:], model.params[p:]):
            y = seg(w, y)
        np.testing.assert_allclose(np.asarray(y), full, rtol=1e-5, atol=1e-5)


CUT_SPEC = PAPER_CNN_SPECS["mobilenetv2"]


@pytest.mark.parametrize("part", range(len(CUT_SPEC.stage_channels) + 1))
def test_engine_cut_matches_reference(part):
    # Every partition point, both ends included: the engine's output equals
    # the plain reference, the suffix output lands on the host device, and
    # the prefix output on the accelerator device (both the CPU here).
    model = build_executable(CUT_SPEC, seed=3)
    n_points = model.num_partition_points
    eng = ServingEngine(
        [model], Plan((part,), (1 if part < n_points else 0,)), k_max=4
    )
    try:
        for s in range(2):
            eng.submit(0, model.make_input(s))
        done = eng.drain(timeout=60.0)
        assert len(done) == 2 and all(c.ok for c in done)
        refs = [reference(model, model.make_input(s)) for s in range(2)]
        errs = match_references([np.asarray(c.output) for c in done], refs, 1)
        assert max(errs) <= 1e-5
        for c in done:
            assert c.output.device == eng.host_device
            if part > 0:
                assert c.prefix_device == eng.accel_device
            else:
                assert c.prefix_device is None
    finally:
        eng.shutdown()


def test_match_references_needs_each_reference_its_number_of_times():
    a, b = np.ones((2, 3)), np.full((2, 3), 2.0)
    assert match_references([b, a, a, b], [a, b], 2) == [0.0] * 4
    with pytest.raises(ValueError):  # one output returned for every request
        match_references([a, a], [a, b], 1)
    with pytest.raises(ValueError):  # a request never completed
        match_references([a, b, b], [a, b], 2)
    assert relative_error(np.zeros(3), a) == float("inf")


def _smoke_model(name: str) -> ExecutableModel:
    """A tenant of ``chip_smoke.py`` with the weights it draws there."""
    return build_executable(
        PAPER_CNN_SPECS[name], seed=chip_smoke.TENANTS.index(name)
    )


def _bf16_pass_conv(x, w, stride=1):
    # One bf16 pass, as a TPU runs a DEFAULT-precision float32 conv:
    # operands rounded to bf16, products accumulated in float32.
    return jax.lax.conv_general_dilated(
        x.astype(jnp.bfloat16),
        w.astype(jnp.bfloat16),
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32,
    )


@pytest.mark.parametrize("name", chip_smoke.TENANTS)
def test_ref_tol_holds_one_bf16_pass(name, monkeypatch):
    # chip_smoke.REF_TOL must admit the chip's one-bf16-pass convs with a
    # margin; the emulation must also move the output, or it proves nothing.
    model = _smoke_model(name)
    xs = [model.make_input(s) for s in range(chip_smoke.N_REQUESTS)]
    refs = [reference(model, x) for x in xs]
    monkeypatch.setattr(cnn, "_conv", _bf16_pass_conv)
    worst = max(relative_error(reference(model, x), r) for x, r in zip(xs, refs))
    assert 1e-3 < worst <= chip_smoke.REF_TOL / 2


@pytest.mark.parametrize("name", chip_smoke.TENANTS)
def test_ref_tol_rejects_a_wrong_stage(name):
    # Every stage's params swapped for another draw, the first two stages
    # run in the other order (same output shape), or the last stage
    # skipped: each must miss chip_smoke.REF_TOL.
    model = _smoke_model(name)
    x = model.make_input(0)
    ref = reference(model, x)
    other = init_cnn(PAPER_CNN_SPECS[name], seed=100)
    segs, params = model.segments, model.params
    wrong = [
        params[:s] + (other[s],) + params[s + 1:] for s in range(len(params))
    ]
    variants = [(segs, p) for p in wrong] + [
        ((segs[1], segs[0]) + segs[2:], params),
        (segs[:-1], params[:-1]),
    ]
    for v_segs, v_params in variants:
        variant = ExecutableModel(model.name, v_segs, v_params, model.make_input)
        assert relative_error(reference(variant, x), ref) > chip_smoke.REF_TOL


def test_set_plan_moves_only_segments_that_cross_the_cut(monkeypatch):
    # A CPU-only process has one device, so stand in two named devices and
    # record every placement set_plan makes.
    model = build_executable(CUT_SPEC, seed=0)
    eng = ServingEngine([model], Plan((2,), (1,)), k_max=4)
    try:
        puts = []

        def device_put(x, device):
            puts.append(device)
            return (device, id(x))

        monkeypatch.setattr(jax, "device_put", device_put)
        eng.accel_device, eng.host_device = "accel", "host"
        eng._placed[0] = None

        def placed(plan):
            puts.clear()
            eng.set_plan(plan)
            return list(puts), [d for d, _ in eng._placed[0]]

        assert placed(Plan((2,), (1,))) == (
            ["accel"] * 2 + ["host"] * 3, ["accel"] * 2 + ["host"] * 3
        )
        before = eng._placed[0]
        assert placed(Plan((2,), (2,))) == ([], ["accel"] * 2 + ["host"] * 3)
        assert placed(Plan((4,), (1,))) == (
            ["accel"] * 2, ["accel"] * 4 + ["host"]
        )
        kept = (0, 1, 4)
        assert all(eng._placed[0][s] is before[s] for s in kept)
        assert placed(Plan((0,), (1,))) == (["host"] * 4, ["host"] * 5)
        assert eng._placed[0][4] is before[4]
    finally:
        eng.shutdown()


def test_engine_refuses_a_process_without_cpu_backend(monkeypatch):
    real = jax.devices

    def devices(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return real(backend)

    monkeypatch.setattr(jax, "devices", devices)
    model = build_executable(CUT_SPEC, seed=0)
    with pytest.raises(RuntimeError, match="no CPU backend"):
        ServingEngine([model], Plan((2,), (1,)), k_max=4)


def test_engine_runs_cnn_mix():
    models = [
        build_executable(PAPER_CNN_SPECS["mobilenetv2"], seed=0),
        build_executable(PAPER_CNN_SPECS["squeezenet"], seed=1),
    ]
    plan = Plan((3, 1), (1, 1))
    eng = ServingEngine(models, plan, k_max=4)
    try:
        for i in range(2):
            for s in range(3):
                eng.submit(i, models[i].make_input(s))
        done = eng.drain(timeout=60.0)
        assert len(done) == 6
        for c in done:
            assert np.all(np.isfinite(np.asarray(c.output)))
    finally:
        eng.shutdown()
