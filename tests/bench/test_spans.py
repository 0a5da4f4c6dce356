"""The readers of the serving engine's spans: on a constructed trace with
overlapping device ops and spans, on a trace without the spans (a program
that writes none), and in a traced run on the CPU."""
import json

import numpy as np
import pytest

from bench import harness, spans, tracing

from .conftest import REPO

SPAN_METRICS = ("worker_wait_ms", "prefix_ms", "pool_wait_ms", "suffix_ms")


def events(with_spans=True):
    # Window [0, 1000) ns; device busy [100,150) u [400,420) u [700,800).
    ops = [(100, 150, "%fusion.1"), (400, 420, "%fusion.2"), (700, 800, "%copy.3")]
    worker = [(-50, 10, "engine.worker_wait"), (30, 50, "engine.worker_wait"),
              (50, 300, "engine.prefix"), (60, 120, "engine.h2d"),
              (120, 200, "engine.launch"), (200, 280, "engine.sync"),
              (380, 450, "engine.prefix"), (385, 400, "engine.h2d"),
              (400, 410, "engine.launch"), (410, 440, "engine.sync"),
              (65, 110, "DevicePutWithSharding")]
    # A pool thread's spans share two names with the worker's; they must
    # not count as the worker's.
    pool = [(300, 310, "engine.pool_wait"), (310, 600, "engine.suffix"),
            (330, 500, "engine.launch"), (500, 590, "engine.sync")]
    host = {"python#0": worker, "python#3": pool,
            "tf_XLAPjRtCpuClient/1#4": [(320, 590, "ThunkExecutor::Execute")]}
    if not with_spans:
        host = {k: [e for e in v if not e[2].startswith("engine.")]
                for k, v in host.items()}
    return tracing.Events(ops=ops, modules=[], host=host, window=(0, 1000))


def reader(metric):
    return harness._reader(harness.reader_path(REPO, metric))


def run_of(ev):
    z = np.zeros(0)
    return harness.Run(plan=None, seconds=1.0, setup_s=0.0, tenant=z, due=z,
                       submit=z, done=z, costs=[], peak=None, traced_s=1.0,
                       events=ev)


def test_idle_split_by_the_innermost_worker_span():
    split = spans.idle_split(events())
    # First prefix [50,300) holds op [100,150): idle 60-100 under h2d, 150-200
    # under launch, 200-280 under sync, 50-60 and 280-300 under the prefix
    # alone.  Second prefix [380,450) holds [400,420): idle 385-400 (h2d),
    # 420-440 (sync), 380-385 and 440-450 (prefix alone).
    assert split == {
        "engine.h2d": pytest.approx((40 + 15) * 1e-9),
        "engine.launch": pytest.approx(50 * 1e-9),
        "engine.sync": pytest.approx((80 + 20) * 1e-9),
        "engine.prefix": pytest.approx((30 + 15) * 1e-9),
        "none": pytest.approx((1000 - 170 - 200 - 50) * 1e-9),
    }
    # The parts and the device's busy time fill the window.
    assert sum(split.values()) + 170e-9 == pytest.approx(1000e-9)


def test_device_idle_in_prefix_frac():
    read = reader("device_idle_in_prefix_frac")
    ev = events()
    assert read(run_of(ev)) == pytest.approx((200 + 50) / 1000)
    # It is the part of device_idle_frac with a prefix open.
    assert read(run_of(ev)) <= tracing.reduce(ev).idle_frac


def test_span_readers_take_the_median_of_spans_that_start_in_the_window():
    got = {m: reader(m)(run_of(events())) for m in SPAN_METRICS}
    # worker_wait: the span that starts before the window is left out;
    # prefix: 250 and 70 ns, nearest-rank median of two is the lower.
    assert got == {"worker_wait_ms": pytest.approx(20e-6),
                   "prefix_ms": pytest.approx(70e-6),
                   "pool_wait_ms": pytest.approx(10e-6),
                   "suffix_ms": pytest.approx(290e-6)}


@pytest.mark.parametrize("metric", SPAN_METRICS + ("device_idle_in_prefix_frac",))
def test_nothing_to_read_without_the_spans_or_a_trace(metric):
    read = reader(metric)
    assert read(run_of(events(with_spans=False))) is None
    assert read(run_of(None)) is None


def test_a_traced_cpu_run_reports_the_engines_phases(tiny_root):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for name in SPAN_METRICS + ("device_idle_in_prefix_frac",):
        spec["per_layer"].append({
            "name": name, "unit": "1" if name.endswith("frac") else "ms",
            "better": "lower", "source": "program_span", "layer": "test",
            "moves": "p50_ms", "workloads": ["tiny.load"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    # This seed gives one all-zero reference (mnasnet's fourth input).
    r = harness.run_cell(tiny_root, "tiny.load", 2**40 + 3, 1.0, True,
                         require_tpu=False, log=lambda m: None)
    assert r["correct"]
    for name in SPAN_METRICS:
        assert r["metrics"][name]["value"] > 0
    # No device plane on the CPU: nothing to read.
    assert "device_idle_in_prefix_frac" not in r["metrics"]
