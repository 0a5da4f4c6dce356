"""The serving engine's tracing on the CPU: per-request phase stamps on its
completion records, the ``engine.*`` spans in a profiler capture, and the
names of its stage programs."""
import functools
import glob
import math
import os
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import arrivals, harness, roofline
from repro.core.planner import Plan
from repro.models.cnn import CNNSpec, build_executable
from repro.serving import engine as engine_mod
from repro.serving.engine import ExecutableModel, ServingEngine
from tests.bench.conftest import TINY_CONFIG

SPEC = CNNSpec("mobilenetv2", (4, 8, 8, 8), in_size=16)
N = len(SPEC.stage_channels)
STAMPS = ("submit_time", "prefix_start", "prefix_end", "suffix_start", "done_time")


def serve(models, plan, submits, *, k_max=4):
    """Submit ``(tenant, input)`` pairs in order; the drained records."""
    eng = ServingEngine(models, plan, k_max=k_max)
    try:
        for m, x in submits:
            eng.submit(m, x)
        return eng.drain(timeout=60.0)
    finally:
        eng.shutdown()


def present(c):
    return [getattr(c, k) for k in STAMPS if not math.isnan(getattr(c, k))]


@pytest.mark.parametrize("part, absent", [
    (0, {"prefix_start", "prefix_end"}),
    (2, set()),
    (N, {"suffix_start"}),
])
def test_stamps_are_ordered_and_the_phases_add_up_to_the_latency(part, absent):
    model = build_executable(SPEC, seed=1)
    plan = Plan((part,), (1 if part < N else 0,))
    done = serve([model], plan, [(0, model.make_input(s)) for s in range(4)])
    assert len(done) == 4
    for c in done:
        assert c.ok
        assert {k for k in STAMPS if math.isnan(getattr(c, k))} == absent
        stamps = present(c)
        assert stamps == sorted(stamps)
        # The phases between consecutive stamps add up to the latency.
        assert math.fsum(np.diff(stamps)) == pytest.approx(c.latency, rel=1e-12)
        # The activation handed across the cut: (1, 8, 8, 8) float32 after
        # two stages, one of them downsampling 16 -> 8.
        assert c.cut_bytes == (8 * 8 * 8 * 4 if 0 < part < N else 0)


def _poisoned_model():
    base = build_executable(SPEC, seed=2)

    def raise_on_poison(_, x):
        if x.shape[0] != 1:       # refused while tracing, as raised
            raise RuntimeError("poisoned input")
        return x

    return ExecutableModel("poison", (base.segments[0], raise_on_poison,
                                      base.segments[1]),
                           (base.params[0], None, base.params[1]),
                           base.make_input)


@pytest.mark.parametrize("part, cores, reached", [
    (3, 0, ("submit_time", "prefix_start")),            # fails in the prefix
    (1, 1, ("submit_time", "prefix_start", "prefix_end", "suffix_start")),
])
def test_an_errored_record_carries_the_stamps_reached_before_the_error(
        part, cores, reached):
    model = _poisoned_model()
    bad = jnp.ones((2, 16, 16, 3))
    done = serve([model], Plan((part,), (cores,)), [(0, bad)])
    assert len(done) == 1 and not done[0].ok
    c = done[0]
    assert tuple(k for k in STAMPS[:-1] if not math.isnan(getattr(c, k))) == reached
    assert present(c) == sorted(present(c))
    # Batch 2, 8x8, the first stage's 4 channels, float32.
    assert c.cut_bytes == (2 * 8 * 8 * 4 * 4 if part < 3 else 0)


@pytest.mark.parametrize("part", [0, 2])
def test_a_refused_pool_submission_closes_its_wait_span(part, monkeypatch):
    opened = []
    real = engine_mod._open_span

    def recording(name, rec):
        span = real(name, rec)
        exits = []
        opened.append((name, exits))
        monkeypatch.setattr(span, "__exit__", lambda *a: exits.append(a),
                            raising=False)
        return span

    monkeypatch.setattr(engine_mod, "_open_span", recording)
    model = build_executable(SPEC, seed=3)
    eng = ServingEngine([model], Plan((part,), (1,)), k_max=4)
    try:
        eng._pools[0].shutdown()
        if part == 0:
            with pytest.raises(RuntimeError):
                eng.submit(0, model.make_input(0))
        else:
            eng.submit(0, model.make_input(0))
        done = eng.drain(timeout=60.0)
    finally:
        eng.shutdown()
    assert len(done) == 1 and not done[0].ok
    assert [name for name, _ in opened][-1] == "engine.pool_wait"
    assert all(len(exits) == 1 for _, exits in opened)


def test_req_ids_are_unique_and_increase_in_submit_order():
    models = [build_executable(SPEC, seed=0), build_executable(SPEC, seed=1)]
    submits = [(s % 2, models[s % 2].make_input(s)) for s in range(8)]
    done = serve(models, Plan((0, 2), (1, 1)), submits)
    by_submit = sorted(done, key=lambda c: c.submit_time)
    ids = [c.req_id for c in by_submit]
    assert ids == sorted(ids) and len(set(ids)) == 8
    assert [c.model_idx for c in by_submit] == [m for m, _ in submits]


def test_cut_bytes_equal_the_benchmarks_out_bytes_at_the_plans_cut():
    cfg = TINY_CONFIG
    params, pools = harness.make_data(cfg, 1, 3, jax.devices()[0])
    n_t = len(cfg["tenants"])
    plan = harness.make_plan(cfg, 40.0 * arrivals.zipf_shares(n_t, 1.0))
    cuts = 0
    done = serve(harness.build_models(cfg, params, None), plan,
                 [(m, np.asarray(pools[m][0])) for m in range(n_t)])
    for c in done:
        t = cfg["tenants"][c.model_idx]
        p, n = plan.partition[c.model_idx], len(t["stage_channels"])
        if 0 < p < n:
            cuts += 1
            assert c.cut_bytes == roofline.stage_costs(cfg, t)[p - 1].out_bytes
        else:
            assert c.cut_bytes == 0
    assert cuts > 0


def test_stage_programs_are_named_by_tenant_and_stage():
    model = build_executable(SPEC, seed=0)
    # A segment that is a partial has no name of its own.
    segs = (functools.partial(model.segments[0]),) + model.segments[1:]
    other = ExecutableModel("mnasnet", segs, model.params, model.make_input)
    eng = ServingEngine([model, other], Plan((2, 2), (1, 1)), k_max=4)
    try:
        for i, name in enumerate(("mobilenetv2", "mnasnet")):
            x = model.make_input(0)
            for s, prog in enumerate(eng._segments[i]):
                text = prog.lower(model.params[s], x).as_text()
                assert f"@jit_{name}_stage{s} " in text
                x = prog(model.params[s], x)
    finally:
        eng.shutdown()


def _capture(tmp_path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    spans = defaultdict(list)     # name -> [(line, start, end, args)]
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("engine."):
                    spans[e.name].append((i, e.start_ns, e.end_ns, dict(e.stats)))
    return spans


def test_a_profiler_capture_holds_the_engines_spans_nested(tmp_path):
    model = build_executable(SPEC, seed=0)
    eng = ServingEngine([model], Plan((2,), (1,)), k_max=4)
    try:
        eng.submit(0, model.make_input(0))        # compiles outside the capture
        eng.drain(timeout=60.0)

        def work():
            for s in range(3):
                eng.submit(0, model.make_input(s))
            assert all(c.ok for c in eng.drain(timeout=60.0))

        spans = _capture(tmp_path, work)
    finally:
        eng.shutdown()

    reqs = {a["req"] for _, _, _, a in spans["engine.prefix"]}
    assert len(reqs) == 3
    for req in reqs:
        def one(name, req=req):
            mine = [x for x in spans[name] if x[3]["req"] == req]
            assert len(mine) == 1, (name, mine)
            assert mine[0][3]["tenant"] == 0
            return mine[0]

        for parent, children in (("engine.prefix", ("engine.h2d",)),
                                 ("engine.suffix", ("engine.cut",))):
            line, lo, hi, _ = one(parent)
            kids = [one(children[0])] + sorted(
                (x for name in ("engine.launch", "engine.sync")
                 for x in spans[name] if x[3]["req"] == req and x[0] == line),
                key=lambda x: x[1])
            # h2d (or cut), launch, sync: in order, on the parent's thread,
            # inside the parent.
            assert len(kids) == 3
            assert all(k[0] == line and lo <= k[1] <= k[2] <= hi for k in kids)
            assert [k[1] for k in kids] == sorted(k[1] for k in kids)
        # Each wait ends before the span it waited for starts.
        assert one("engine.worker_wait")[2] <= one("engine.prefix")[1]
        assert one("engine.prefix")[1] <= one("engine.pool_wait")[1]
        assert one("engine.pool_wait")[2] <= one("engine.suffix")[1]
