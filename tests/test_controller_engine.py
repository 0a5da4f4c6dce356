"""Tests for the online controller (adaptive re-planning) and the
real-execution serving engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.allocator import edge_tpu_compiler_plan, hill_climb
from repro.core.planner import Plan, TenantSpec
from repro.configs.paper_models import paper_profile
from repro.hw.specs import EDGE_TPU_PLATFORM
from repro.serving.controller import (
    SlidingRateEstimator,
    _should_cold_fallback,
    run_adaptive,
)
from repro.serving.engine import ExecutableModel, ServingEngine
from repro.serving.simulator import simulate
from repro.serving.workload import (
    RatePhase,
    Trace,
    dynamic_trace,
    poisson_trace,
)

HW = EDGE_TPU_PLATFORM
K_MAX = HW.cpu.n_cores


class TestRateEstimator:
    def test_basic_rate(self):
        est = SlidingRateEstimator(1, window=10.0)
        for t in np.arange(0.0, 10.0, 0.5):
            est.observe(0, float(t))
        assert est.rates(10.0)[0] == pytest.approx(2.0)

    def test_window_expiry(self):
        est = SlidingRateEstimator(1, window=5.0)
        est.observe(0, 0.0)
        est.observe(0, 8.0)
        assert est.rates(10.0)[0] == pytest.approx(1 / 5.0)

    def test_partial_window_divides_by_elapsed_time(self):
        # 3 arrivals in the first second with a 30 s window: lambda-hat is
        # 3/s, not 0.1/s (the pre-fix bug divided by the full window before
        # one window had elapsed).
        est = SlidingRateEstimator(1, window=30.0)
        for t in (0.1, 0.5, 0.9):
            est.observe(0, t)
        assert est.rates(1.0)[0] == pytest.approx(3.0)

    def test_full_window_unchanged(self):
        est = SlidingRateEstimator(1, window=10.0)
        for t in np.arange(0.0, 40.0, 0.5):
            est.observe(0, float(t))
        assert est.rates(40.0)[0] == pytest.approx(2.0)

    def test_time_zero_no_division_by_zero(self):
        est = SlidingRateEstimator(2, window=30.0)
        est.observe(0, 0.0)
        assert est.rates(0.0) == [0.0, 0.0]

    def test_backdated_probe_is_monotone_safe(self):
        # rates(t1) evicts stamps older than t1 - window; a later probe at
        # t0 < t1 used to answer from the already-evicted window (an
        # eviction-order-dependent estimate).  The clock now clamps to its
        # high-water mark: the backdated probe answers at t1, and probing
        # forward again is unchanged.
        est = SlidingRateEstimator(1, window=10.0)
        for t in (1.0, 2.0, 14.0, 15.0):
            est.observe(0, t)
        at_t1 = est.rates(16.0)  # evicts the 1.0/2.0 stamps
        assert at_t1[0] == pytest.approx(2 / 10.0)
        assert est.rates(8.0) == at_t1  # backdated probe: clamped, stable
        assert est.rates(16.0) == at_t1

    def test_boundary_stamp_is_idempotent(self):
        # A stamp sitting exactly on the window edge (dq[0] == now - window)
        # is kept by the strict < eviction; repeated evaluation at the same
        # instant must count it every time, not evict it on the first pass
        # and lose it on the second.
        est = SlidingRateEstimator(1, window=10.0)
        est.observe(0, 5.0)
        est.observe(0, 12.0)
        first = est.rates(15.0)  # 5.0 == 15.0 - 10.0: on the boundary
        assert first[0] == pytest.approx(2 / 10.0)
        assert est.rates(15.0) == first
        assert est.rates(15.0) == first

    # -- exponential-decay weighting (opt-in, PR 8) --

    def test_decay_requires_positive(self):
        with pytest.raises(ValueError):
            SlidingRateEstimator(1, window=10.0, decay=0.0)
        with pytest.raises(ValueError):
            SlidingRateEstimator(1, window=10.0, decay=-1.0)

    def test_decay_matches_closed_form(self):
        # Pins the estimator's exact semantics: each stamp at age ``a``
        # weighs exp(-a/tau) and the normalizer is the kernel's integral
        # over the observed horizon, tau * (1 - exp(-horizon/tau)).
        tau, now, window = 5.0, 10.0, 30.0
        stamps = (1.0, 2.0, 3.0, 7.5)
        est = SlidingRateEstimator(1, window=window, decay=tau)
        for t in stamps:
            est.observe(0, t)
        horizon = min(window, now)
        expected = sum(np.exp((t - now) / tau) for t in stamps) / (
            tau * (1.0 - np.exp(-horizon / tau))
        )
        assert est.rates(now)[0] == pytest.approx(expected)

    def test_decay_unbiased_for_stationary_arrivals(self):
        # A steady 2/s stream over a full window estimates ~2/s regardless
        # of tau (the normalizer makes the weighted count unbiased).
        for tau in (3.0, 10.0, 100.0):
            est = SlidingRateEstimator(1, window=30.0, decay=tau)
            for t in np.arange(0.0, 30.0, 0.5):
                est.observe(0, float(t))
            assert est.rates(30.0)[0] == pytest.approx(2.0, rel=0.1)

    def test_decay_steps_down_faster_than_uniform(self):
        # Regression (the burst-decay bias): after a 10/s burst ends and
        # traffic settles at 1/s, the uniform window stays inflated until
        # the burst stamps age out, while the decayed estimate has already
        # relaxed close to the true post-step rate.
        def feed(est):
            for t in np.arange(0.0, 10.0, 0.1):  # 10/s burst in [0, 10)
                est.observe(0, float(t))
            for t in np.arange(10.0, 30.0, 1.0):  # 1/s tail in [10, 30)
                est.observe(0, float(t))
            return est.rates(30.0)[0]

        plain = feed(SlidingRateEstimator(1, window=30.0))
        decayed = feed(SlidingRateEstimator(1, window=30.0, decay=5.0))
        assert plain == pytest.approx(120 / 30.0)  # still burst-inflated
        assert decayed < plain
        assert abs(decayed - 1.0) < abs(plain - 1.0)
        assert decayed == pytest.approx(1.0, rel=0.5)

    def test_decay_none_is_bitwise_default(self):
        a = SlidingRateEstimator(1, window=10.0)
        b = SlidingRateEstimator(1, window=10.0, decay=None)
        for t in (0.5, 1.0, 4.0, 9.0):
            a.observe(0, t)
            b.observe(0, t)
        assert a.rates(9.5) == b.rates(9.5)


class TestAdaptiveController:
    def test_adapts_and_beats_static_full_tpu(self):
        # MnasNet + InceptionV4 with rate step-ups, as in Fig. 8.
        profiles = [paper_profile("mnasnet"), paper_profile("inceptionv4")]
        phases = [
            RatePhase(0.0, 300.0, (5.0, 1.0)),
            RatePhase(300.0, 600.0, (5.0, 3.0)),
            RatePhase(600.0, 900.0, (5.0, 5.0)),
        ]
        trace = dynamic_trace(phases, seed=0)
        res = run_adaptive(
            profiles,
            trace,
            HW,
            K_MAX,
            replan_period=30.0,
            window=30.0,
            initial_rates=(5.0, 1.0),
        )
        assert len(res.plans) > 1
        # Planner stays cheap (paper: <2ms; allow slack for CI noise).
        assert max(res.plan_compute_seconds) < 0.05
        # Compare with the static default-compiler plan on the same trace.
        tenants = [TenantSpec(p, 3.0) for p in profiles]
        static = simulate(tenants, edge_tpu_compiler_plan(tenants), HW, trace)
        assert res.sim.overall_mean() < static.overall_mean()

    def test_replans_on_schedule(self):
        profiles = [paper_profile("mnasnet")]
        phases = [RatePhase(0.0, 120.0, (2.0,))]
        trace = dynamic_trace(phases, seed=1)
        res = run_adaptive(
            profiles, trace, HW, K_MAX, replan_period=30.0, initial_rates=(2.0,)
        )
        assert len(res.replan_times) >= 3

    def test_warmup_frac_excludes_leading_requests(self):
        profiles = [paper_profile("mnasnet")]
        phases = [RatePhase(0.0, 120.0, (2.0,))]
        trace = dynamic_trace(phases, seed=2)
        full = run_adaptive(
            profiles, trace, HW, K_MAX, initial_rates=(2.0,), warmup_frac=0.0
        )
        trimmed = run_adaptive(
            profiles, trace, HW, K_MAX, initial_rates=(2.0,), warmup_frac=0.5
        )
        n_full = len(full.sim.latencies[0])
        n_trim = len(trimmed.sim.latencies[0])
        assert n_full == len(trace)
        assert 0 < n_trim < n_full
        # Only requests arriving past the warmup horizon are recorded.
        horizon = max(r.arrival for r in trace)
        assert min(trimmed.sim.arrivals[0]) >= 0.5 * horizon

    def test_replan_tick_tie_timestamp_determinism(self):
        # Regression pin: an arrival landing *exactly* on a re-plan tick
        # must be observed on a fixed side of the plan switch in both
        # drivers.  Both resolve the boundary with a strict `<` cut
        # (scalar: `fire_due_replans` fires before any arrival with
        # `t >= next_replan` is observed; columnar: `searchsorted(...,
        # side="left")` ends the span before the tying arrival), so the
        # tying request is always served under the NEW plan and counted
        # toward the NEW window.  Identical plans and a bitwise-identical
        # SimResult across the two paths is the contract.
        profiles = [paper_profile("mnasnet"), paper_profile("inceptionv4")]
        rng = np.random.default_rng(7)
        n = 400
        arr = np.sort(rng.uniform(0.0, 120.0, n))
        # Plant exact tie timestamps on the 30s re-plan grid.  Replacing
        # the first arrival at or after each tick keeps the column sorted.
        for tick in (30.0, 60.0, 90.0):
            arr[np.searchsorted(arr, tick)] = tick
        mi = rng.integers(0, 2, n)
        trace = Trace(mi, arr)
        assert {30.0, 60.0, 90.0} <= set(arr.tolist())

        common = dict(
            replan_period=30.0, window=30.0, initial_rates=(2.0, 2.0)
        )
        col = run_adaptive(profiles, trace, HW, K_MAX, vectorize=True,
                           **common)
        seq = run_adaptive(profiles, trace, HW, K_MAX, vectorize=False,
                           **common)

        assert col.replan_times == seq.replan_times
        assert col.plans == seq.plans
        assert len(col.plans) > 1  # the ticks actually re-planned
        # Bitwise-identical observations: the columnar driver hands the
        # estimator and simulator the same requests on the same side of
        # every boundary as the scalar loop.  Sole documented exception
        # (run_trace docstring, test_sim_fastpath.assert_bitwise_equal):
        # the aggregate ``tpu_busy`` sums pairwise instead of
        # sequentially, equal to round-off only.
        assert col.sim.tpu_busy == pytest.approx(seq.sim.tpu_busy,
                                                 rel=1e-12)
        assert col.sim.duration == seq.sim.duration
        assert col.sim.misses == seq.sim.misses
        assert col.sim.tpu_requests == seq.sim.tpu_requests
        for m in range(len(profiles)):
            np.testing.assert_array_equal(
                np.asarray(col.sim.latencies[m]),
                np.asarray(seq.sim.latencies[m]))
            np.testing.assert_array_equal(
                np.asarray(col.sim.arrivals[m]),
                np.asarray(seq.sim.arrivals[m]))

    def test_adaptive_utilization_never_exceeds_one(self):
        # Overload phase: the backlog drains past the last arrival; the
        # duration fix keeps observed utilization physical.
        profiles = [paper_profile("inceptionv4")]
        phases = [RatePhase(0.0, 60.0, (60.0,))]
        trace = dynamic_trace(phases, seed=3)
        res = run_adaptive(profiles, trace, HW, K_MAX, initial_rates=(60.0,))
        assert res.sim.tpu_utilization <= 1.0
        assert res.sim.duration >= max(r.arrival for r in trace)

    def test_replans_warm_start_from_incumbent(self):
        # The controller passes the incumbent plan to warm-capable planners.
        profiles = [paper_profile("mnasnet"), paper_profile("inceptionv4")]
        seen: list[Plan | None] = []

        def spy_planner(tenants, platform, k_max, *, tables=None, init_plan=None):
            seen.append(init_plan)
            return hill_climb(
                tenants, platform, k_max, tables=tables, init_plan=init_plan
            )

        phases = [RatePhase(0.0, 120.0, (5.0, 1.0))]
        trace = dynamic_trace(phases, seed=4)
        res = run_adaptive(
            profiles,
            trace,
            HW,
            K_MAX,
            replan_period=30.0,
            initial_rates=(5.0, 1.0),
            planner=spy_planner,
            # Guard off: a fallback would add cold planner invocations and
            # this test pins the *warm-start threading* one-call-per-replan
            # contract (the guard has its own tests below).
            cold_fallback_margin=None,
        )
        assert seen[0] is None                      # cold initial plan
        assert len(seen) == len(res.plans)
        assert all(p is not None for p in seen[1:])  # re-plans warm-started
        for incumbent, prev in zip(seen[1:], res.plans):
            assert incumbent == prev


# The warm-start quality tail (ROADMAP): cold-planning this mix at DRIFT_R0,
# then warm-descending after the rates drift to DRIFT_R1, lands in a basin
# >5% worse than a cold re-climb.  Found by random search over paper-model
# mixes; robust to +-5% rate perturbation.
DRIFT_MODELS = ("densenet201", "mobilenetv2", "squeezenet")
DRIFT_R0 = (2.2, 1.0, 3.2)
DRIFT_R1 = (11.4, 1.3, 2.9)


class TestColdFallbackGuard:
    def test_warm_tail_reproduction(self):
        # Regression for the quality tail itself: warm descent from the
        # stale incumbent lands >5% worse than the cold climb.
        profs = [paper_profile(n) for n in DRIFT_MODELS]
        t0 = [TenantSpec(p, r) for p, r in zip(profs, DRIFT_R0)]
        t1 = [TenantSpec(p, r) for p, r in zip(profs, DRIFT_R1)]
        plan0, obj0 = hill_climb(t0, HW, K_MAX)
        _, warm = hill_climb(t1, HW, K_MAX, init_plan=plan0)
        _, cold = hill_climb(t1, HW, K_MAX)
        assert warm > 1.05 * cold
        # The guard detects the regression from the incumbent's trend and
        # taking the better of warm/cold recovers the cold optimum.
        norm_hist = [obj0 / sum(DRIFT_R0)]
        assert _should_cold_fallback(warm / sum(DRIFT_R1), norm_hist, 0.05)
        assert min(warm, cold) == cold

    def test_should_cold_fallback_edge_cases(self):
        assert not _should_cold_fallback(10.0, [], 0.05)      # no trend yet
        assert not _should_cold_fallback(1.04, [1.0], 0.05)   # within margin
        assert _should_cold_fallback(1.06, [1.0], 0.05)
        # The trend is the *median* of the recent re-plans: one lucky low
        # estimate must not make ordinary noise look like a regression.
        assert not _should_cold_fallback(1.2, [2.0, 1.0, 1.5], 0.05)
        assert _should_cold_fallback(1.6, [2.0, 1.0, 1.5], 0.05)

    def test_run_adaptive_guard_recovers_drift_regression(self):
        # Integration: the trace runs at the drifted rates while the initial
        # plan is the stale cold plan for the old rates; every re-plan's
        # warm descent lands in the bad basin and the guard's cold fallback
        # recovers >5% of predicted objective (deterministic: seeded trace,
        # deterministic planner).
        profs = [paper_profile(n) for n in DRIFT_MODELS]
        trace = poisson_trace(list(DRIFT_R1), 100.0, seed=3)
        common = dict(
            replan_period=30.0, window=30.0, initial_rates=DRIFT_R0
        )
        guarded = run_adaptive(
            profs, trace, HW, K_MAX, cold_fallback_margin=0.05, **common
        )
        plain = run_adaptive(
            profs, trace, HW, K_MAX, cold_fallback_margin=None, **common
        )
        assert guarded.cold_fallback_times == [30.0, 60.0, 90.0]
        assert not plain.cold_fallback_times
        # Identical rate estimates in both runs (the estimator only sees the
        # trace), so per-replan objectives are directly comparable.
        assert len(guarded.plan_objectives) == len(plain.plan_objectives)
        for g, p in zip(guarded.plan_objectives[1:], plain.plan_objectives[1:]):
            assert g <= p * (1 + 1e-12)
        best_recovery = max(
            (p - g) / p
            for g, p in zip(guarded.plan_objectives[1:], plain.plan_objectives[1:])
        )
        assert best_recovery > 0.05

    def test_guard_quiet_on_stationary_load(self):
        # No drift: warm re-plans track the incumbent trend (the median of
        # recent normalized objectives) and a margin above the estimator
        # noise keeps the guard silent.
        profiles = [paper_profile("mnasnet"), paper_profile("inceptionv4")]
        phases = [RatePhase(0.0, 300.0, (5.0, 1.0))]
        for seed in (11, 12, 13):
            trace = dynamic_trace(phases, seed=seed)
            res = run_adaptive(
                profiles, trace, HW, K_MAX,
                replan_period=30.0, window=30.0, initial_rates=(5.0, 1.0),
                cold_fallback_margin=0.25,
            )
            assert res.cold_fallback_times == []
            assert len(res.plan_objectives) == len(res.plans)


class TestAdaptiveDesBackend:
    def test_des_backend_adapts_and_matches_stepper_stats(self):
        profiles = [paper_profile("mnasnet"), paper_profile("inceptionv4")]
        phases = [
            RatePhase(0.0, 200.0, (5.0, 1.0)),
            RatePhase(200.0, 400.0, (5.0, 4.0)),
        ]
        trace = dynamic_trace(phases, seed=21)
        common = dict(
            replan_period=30.0, window=30.0, initial_rates=(5.0, 1.0)
        )
        des = run_adaptive(profiles, trace, HW, K_MAX, backend="des", **common)
        step = run_adaptive(
            profiles, trace, HW, K_MAX, backend="stepper", **common
        )
        assert len(des.plans) > 1
        assert des.sim.tpu_utilization <= 1.0
        assert sum(len(l) for l in des.sim.latencies) == sum(
            len(l) for l in step.sim.latencies
        )
        # Two independent runtimes under the same controller: statistics
        # agree even though event mechanics differ.
        assert des.sim.overall_mean() == pytest.approx(
            step.sim.overall_mean(), rel=0.1
        )
        # Same rate estimates -> same re-plans on both backends.
        assert des.plans == step.plans


def _make_mlp_model(name: str, n_segments: int, dim: int, seed: int) -> ExecutableModel:
    rng = np.random.default_rng(seed)
    weights = tuple(
        rng.standard_normal((dim, dim), dtype=np.float32) / np.float32(np.sqrt(dim))
        for _ in range(n_segments)
    )

    def seg(w, x):
        return jnp.tanh(x @ w)

    return ExecutableModel(
        name=name,
        segments=(seg,) * n_segments,
        params=weights,
        make_input=lambda s: np.random.default_rng(s).standard_normal(
            (1, dim), dtype=np.float32
        ),
    )


class TestServingEngine:
    def test_end_to_end_execution_matches_sequential(self):
        models = [_make_mlp_model("a", 4, 32, 0), _make_mlp_model("b", 3, 32, 1)]
        plan = Plan((2, 1), (1, 1))
        eng = ServingEngine(models, plan, k_max=4)
        try:
            inputs = []
            for i, m in enumerate(models):
                for s in range(5):
                    x = m.make_input(s)
                    inputs.append((i, x))
                    eng.submit(i, x)
            done = eng.drain(timeout=30.0)
            assert len(done) == len(inputs)
            # Outputs must equal the plain sequential forward pass.
            by_model = {}
            for c in done:
                by_model.setdefault(c.model_idx, []).append(c)
            for i, m in enumerate(models):
                outs = {np.asarray(c.output).tobytes() for c in by_model[i]}
                expect = set()
                for s in range(5):
                    x = m.make_input(s)
                    for seg, w in zip(eng._segments[i], m.params):
                        x = seg(w, x)
                    expect.add(np.asarray(x).tobytes())
                assert outs == expect
        finally:
            eng.shutdown()

    def test_full_cpu_and_full_tpu_paths(self):
        models = [_make_mlp_model("a", 3, 16, 0), _make_mlp_model("b", 3, 16, 1)]
        plan = Plan((0, 3), (2, 0))  # model 0 all-CPU, model 1 all-TPU
        eng = ServingEngine(models, plan, k_max=4)
        try:
            for i in range(2):
                eng.submit(i, models[i].make_input(0))
            done = eng.drain(timeout=30.0)
            assert len(done) == 2
        finally:
            eng.shutdown()

    def test_plan_switch_live(self):
        models = [_make_mlp_model("a", 4, 16, 0)]
        eng = ServingEngine(models, Plan((4,), (0,)), k_max=4)
        try:
            eng.submit(0, models[0].make_input(0))
            eng.drain(timeout=30.0)
            eng.set_plan(Plan((2,), (2,)))
            eng.submit(0, models[0].make_input(1))
            done = eng.drain(timeout=30.0)
            assert len(done) == 1
        finally:
            eng.shutdown()

    def test_rejects_bad_plan(self):
        models = [_make_mlp_model("a", 2, 8, 0)]
        with pytest.raises(ValueError):
            ServingEngine(models, Plan((1, 1), (1, 1)), k_max=4)

    def test_segment_exception_surfaces_and_engine_survives(self):
        # A segment that raises must become an errored CompletedRequest --
        # not a dead worker thread holding the in-flight count forever.
        base = _make_mlp_model("a", 2, 16, 0)

        def raise_on_poison(_, x):
            # The engine jits every segment, so the poison is a batch size
            # this segment refuses while tracing: its RuntimeError reaches
            # the record as raised.
            if x.shape[0] != 1:
                raise RuntimeError("poisoned input")
            return x

        model = ExecutableModel(
            name="poison",
            segments=(base.segments[0], raise_on_poison, base.segments[1]),
            params=(base.params[0], None, base.params[1]),
            make_input=base.make_input,
        )
        # (partition, cores): all-prefix exercises the TPU-worker except
        # path; split exercises the CPU suffix-pool except path (the poison
        # rides through the first segment into the raising one).
        for part, cores in ((3, 0), (1, 1)):
            eng = ServingEngine([model], Plan((part,), (cores,)), k_max=4)
            try:
                good = model.make_input(0)
                bad = jnp.ones((2, 16))
                eng.submit(0, good)
                eng.submit(0, bad)
                eng.submit(0, good)
                done = eng.drain(timeout=30.0)
                assert len(done) == 3
                errs = [c for c in done if not c.ok]
                assert len(errs) == 1
                assert isinstance(errs[0].error, RuntimeError)
                assert "poisoned input" in str(errs[0].error)
                assert errs[0].output is None
                assert all(c.error is None for c in done if c.ok)
                # The engine keeps serving after the failure.
                eng.submit(0, good)
                done2 = eng.drain(timeout=30.0)
                assert len(done2) == 1 and done2[0].ok
            finally:
                eng.shutdown()

    def test_sync_dispatch_failure_releases_inflight_slot(self):
        # Synchronous zero-prefix dispatch failures must both propagate to
        # the submitter and release the in-flight slot so drain() returns.
        models = [_make_mlp_model("a", 2, 8, 0)]
        eng = ServingEngine(models, Plan((0,), (1,)), k_max=4)
        try:
            pool = eng._pools[0]
            eng._pools[0] = None  # simulate a lost suffix pool
            with pytest.raises(RuntimeError):
                eng.submit(0, models[0].make_input(0))
            done = eng.drain(timeout=5.0)
            assert len(done) == 1 and not done[0].ok
            eng._pools[0] = pool
            eng.submit(0, models[0].make_input(1))
            done = eng.drain(timeout=30.0)
            assert len(done) == 1 and done[0].ok
        finally:
            eng.shutdown()

    def test_serve_exit_path_fails_on_errored_record(self, capsys):
        from repro.launch.serve import report_execution
        from repro.serving.engine import CompletedRequest

        good = CompletedRequest(0, 0.0, 0.5, np.zeros(1))
        bad = CompletedRequest(1, 0.0, 9.0, None, error=RuntimeError("boom"))
        report_execution([good], ["a", "b"])  # all ok: returns normally
        with pytest.raises(SystemExit) as exc:
            report_execution([good, bad], ["a", "b"])
        assert exc.value.code not in (0, None)
        out = capsys.readouterr().out
        # The errored record's 9 s never enters b's latency mean.
        assert "b              n=0" in out and "boom" in out
