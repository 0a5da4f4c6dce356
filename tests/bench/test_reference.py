"""The benchmark's plain reference against the program's own float32
reference at a small size, and what the check's limit catches."""
import dataclasses

import jax
import numpy as np
import pytest

from bench import harness, reference
from repro.models.cnn import CNNSpec, build_executable
from repro.models.cnn import reference as program_reference

from .conftest import REPO, TINY_CONFIG

LIMIT = TINY_CONFIG["check"]["max_rel_err"]


@pytest.fixture(scope="module")
def tenant():
    """The mnasnet stand-in of the tiny configuration, weights and inputs
    from the benchmark's seed, on the CPU."""
    t = TINY_CONFIG["tenants"][1]
    conv_chain = harness.family(REPO, TINY_CONFIG)
    params, pools = conv_chain.make_data(TINY_CONFIG, 4, 123, jax.devices("cpu")[0])
    params, pool = jax.device_get(params[1]), np.asarray(pools[1])
    spec = CNNSpec(t["name"], tuple(t["stage_channels"]), in_size=t["input_size"])
    model = dataclasses.replace(build_executable(spec), params=tuple(params))
    return model, params, pool


def run(params, pool, **kw):
    st = tuple(reference.strides(TINY_CONFIG, len(params)))
    return [np.asarray(reference.forward(params, x, strides=st, **kw)) for x in pool]


def test_matches_the_programs_reference(tenant):
    model, params, pool = tenant
    ours = reference.references(TINY_CONFIG, [params], [pool])[0]
    for x, y in zip(pool, ours):
        assert reference.relative_error(program_reference(model, x), y) < 1e-5


@pytest.mark.parametrize("fault", ["redrawn", "skipped", "reordered", "identity"])
def test_a_wrong_stage_fails_the_limit(tenant, fault):
    _, params, pool = tenant
    bad = list(params)
    if fault == "redrawn":
        rng = np.random.default_rng(0)
        bad[3] = jax.tree.map(lambda a: rng.standard_normal(a.shape, a.dtype)
                              / np.sqrt(a.size / a.shape[-1]), bad[3])
        out = run(bad, pool)
    elif fault == "skipped":
        out = run(bad[:-1], pool)
    elif fault == "reordered":
        bad[2], bad[3] = bad[3], bad[2]
        out = run(bad, pool)
    else:
        # A stage that returns its input unchanged (stage 2 keeps 8 channels
        # and, at stride 2, would halve the size: use stride-1 stage 3).
        st = reference.strides(TINY_CONFIG, len(params))
        out = []
        for x in pool:
            y = x
            for i, (p, s) in enumerate(zip(params, st)):
                y = y if i == 3 else reference.stage(p, y, stride=s)
            out.append(np.asarray(y))
    refs = run(params, pool)
    errs = [reference.relative_error(o, r) for o, r in zip(out, refs)]
    assert max(errs) > LIMIT


def test_controls(tenant):
    _, params, pool = tenant
    refs = run(params, pool)
    fp8 = run(params, pool, precision="fp8")
    bf16 = run(params, pool, precision="bfloat16")
    assert max(reference.relative_error(o, r) for o, r in zip(fp8, refs)) > LIMIT
    assert max(reference.relative_error(o, r) for o, r in zip(bf16, refs)) < LIMIT


def test_relative_error_refuses_wrong_shape_and_nan():
    ref = np.ones((1, 2, 2, 3), np.float32)
    assert reference.relative_error(np.ones((1, 2, 2, 4)), ref) == float("inf")
    assert reference.relative_error(np.full_like(ref, np.nan), ref) == float("inf")
    assert reference.relative_error(ref * 1.01, ref) == pytest.approx(0.01, rel=1e-3)


def test_relative_error_against_an_all_zero_reference():
    zero = np.zeros((1, 2, 2, 3), np.float32)
    assert reference.relative_error(zero, zero) == 0.0
    y = zero.copy()
    y[0, 1, 0, 2] = 1e-30
    assert reference.relative_error(y, zero) == float("inf")
    assert reference.relative_error(-y, zero) == float("inf")
    # A reference with one non-zero entry keeps the plain ratio.
    ref = zero.copy()
    ref[0, 0, 0, 0] = 2.0
    assert reference.relative_error(zero, ref) == 1.0
