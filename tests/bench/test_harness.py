"""A cell is found by name in files; a run on the CPU (the chip check
skipped) drives the whole path and checks every output; the entry point
refuses a process without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

from .conftest import REPO, TINY_MIX


def test_cell_loads_from_files_found_by_name(tiny_root):
    cell = harness.load_cell(tiny_root, "tiny.load")
    assert cell.config["name"] == "tiny" and cell.mix["name"] == "tiny_mix"
    assert [m["name"] for m in cell.end_to_end] == ["p50_ms", "p99_ms", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "gen_late_p99_ms", "cut_kb_per_req", "device_idle_frac"}


@pytest.mark.parametrize("missing", ["config", "traffic", "metric"])
def test_a_missing_file_is_an_error(tiny_root, missing):
    path = {"config": tiny_root / "bench/configs/tiny.json",
            "traffic": tiny_root / "bench/mixes/tiny_mix.json",
            "metric": tiny_root / "bench/metrics/p99_ms.py"}[missing]
    path.unlink()
    with pytest.raises(FileNotFoundError):
        harness.load_cell(tiny_root, "tiny.load")


def test_an_unknown_cell_is_an_error(tiny_root):
    with pytest.raises(KeyError):
        harness.load_cell(tiny_root, "tiny.nothing")


def test_a_new_cell_mix_and_metric_need_only_new_files(tiny_root):
    """Add a mix, a per-layer metric with its reader, and a cell, by new
    files and new entries; no existing file changes."""
    before = {p: p.read_bytes() for p in (tiny_root / "bench").rglob("*") if p.is_file()}
    (tiny_root / "bench/mixes/tiny_burst.json").write_text(json.dumps(dict(
        TINY_MIX, name="tiny_burst", arrivals="mmpp", burst_factor=4.0,
        mean_normal_s=0.4, mean_burst_s=0.1)))
    (tiny_root / "bench/metrics/requests_offered.py").write_text(
        '"""Requests offered in the window."""\n\n\n'
        "def read(run):\n    return float(len(run.due))\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.burst", "config": "tiny",
                              "traffic": "tiny_burst", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "requests_offered", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "load generator", "moves": "p99_ms",
                              "workloads": ["tiny.burst"]})
    for m in spec["end_to_end"]:
        m["workloads"].append("tiny.burst")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    r = harness.run_cell(tiny_root, "tiny.burst", 77, 1.0, True,
                         require_tpu=False, log=lambda m: None)
    assert r["correct"]
    assert r["metrics"]["requests_offered"]["value"] == r["attempted"] == 40
    after = {p: p.read_bytes() for p in before}
    assert after == before


TOY_CONFIG = {
    "name": "toy", "family": "toy_dense", "source": "test", "k_max": 4,
    "tenants": [
        {"name": "dense_a", "widths": [8, 16, 16, 8], "lengths": [4, 6],
         "partition": 1, "cores": 1},
        {"name": "dense_b", "widths": [8, 12, 8], "lengths": [3, 5],
         "partition": 1, "cores": 2},
    ],
    "check": {"max_rel_err": 1e-4},
}


def add_toy_family(root):
    """Add a family, a configuration of it, a mix and a cell by new files
    and new entries; the bench files that were there before, by path."""
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    shutil.copy(REPO / "tests/bench/toy_dense.py",
                root / "bench/families/toy_dense.py")
    (root / "bench/configs/toy.json").write_text(json.dumps(TOY_CONFIG))
    (root / "bench/mixes/toy_mix.json").write_text(
        json.dumps(dict(TINY_MIX, name="toy_mix", rate_rps=60.0)))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "test",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "toy.load", "config": "toy",
                              "traffic": "toy_mix", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        m["workloads"].append("toy.load")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return before


def test_a_new_family_needs_only_new_files(tiny_root):
    before = add_toy_family(tiny_root)
    r = harness.run_cell(tiny_root, "toy.load", 2**33 + 9, 1.0, False,
                         require_tpu=False, log=lambda m: None)
    assert r["correct"], r["check"]
    assert r["attempted"] == 60 and r["failed"] == 0
    assert r["check"]["max_rel_err"]["value"] < 1e-5
    # Warm-up sent each tenant's two input lengths: nothing compiles later.
    assert r["window"]["compiles_in_window"] == 0
    assert r["window"]["plan"]["partition"] == [1, 1]
    assert set(r["metrics"]) == {"p50_ms", "p99_ms", "setup_s"}

    r = harness.run_cell(tiny_root, "toy.load", 6, 1.0, True,
                         require_tpu=False, log=lambda m: None)
    assert r["correct"], r["check"]
    # Both tenants are cut after their first segment, at a mean length of 5
    # and 4 tokens and a width of 16 and 12 float32 values.
    cut = r["metrics"]["cut_kb_per_req"]["value"]
    assert 4 * 4 * 12 / 1e3 < cut < 4 * 5 * 16 / 1e3
    assert "gen_late_p99_ms" in r["metrics"]
    # No device plane on the CPU: the device's metrics have nothing to read.
    assert "device_idle_frac" not in r["metrics"]
    assert {p: p.read_bytes() for p in before} == before


def test_a_new_familys_control_is_not_correct(tiny_root):
    add_toy_family(tiny_root)
    r = harness.run_cell(tiny_root, "toy.load", 12, 1.0, False,
                         require_tpu=False, control="bfloat16", log=lambda m: None)
    assert not r["correct"]
    assert r["check"]["max_rel_err"]["value"] > r["check"]["max_rel_err"]["limit"]


def test_a_control_the_family_does_not_list_is_refused(tiny_root):
    with pytest.raises(ValueError, match="no control"):
        harness.run_cell(tiny_root, "tiny.load", 1, 1.0, False,
                         require_tpu=False, control="int4", log=lambda m: None)


def test_an_unknown_family_is_an_error(tiny_root):
    path = tiny_root / "bench/configs/tiny.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    family="no_such_family")))
    with pytest.raises(FileNotFoundError, match="no_such_family"):
        harness.load_cell(tiny_root, "tiny.load")


def test_a_cpu_run_drives_the_path_and_checks_every_output(tiny_root):
    r = harness.run_cell(tiny_root, "tiny.load", 2**33 + 1, 1.0, False,
                         require_tpu=False, log=lambda m: None)
    assert r["correct"], r["check"]
    assert r["attempted"] == 40 and r["failed"] == 0
    assert set(r["metrics"]) == {"p50_ms", "p99_ms", "setup_s"}
    assert r["metrics"]["p99_ms"]["value"] >= r["metrics"]["p50_ms"]["value"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert list(r)[-1] == "check"
    assert r["check"]["max_rel_err"]["value"] < 1e-5   # both sides on the CPU
    assert r["window"]["compiles_in_window"] == 0
    # The plan cuts inside a tenant, so the check covers prefix, cut and suffix.
    parts = r["window"]["plan"]["partition"]
    assert any(0 < p < n for p, n in zip(parts, (5, 7)))


def test_a_traced_cpu_run_reports_per_layer_metrics(tiny_root):
    r = harness.run_cell(tiny_root, "tiny.load", 5, 1.0, True,
                         require_tpu=False, log=lambda m: None)
    assert r["correct"]
    assert "cut_kb_per_req" in r["metrics"] and "gen_late_p99_ms" in r["metrics"]
    # No device plane on the CPU: the device's metrics have nothing to read.
    assert "device_idle_frac" not in r["metrics"]
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def _run_py(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", spec["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_exits_nonzero_without_a_tpu():
    p = _run_py(REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_py_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for d in spec["paths"]:
        shutil.copytree(REPO / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""

