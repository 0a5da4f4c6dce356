"""Shared set-up of the benchmark's CPU tests: the repository root on the
path, and a small benchmark tree (one two-tenant cell at 16x16 inputs)
built in a temporary directory from files, as a later cell would be."""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_CONFIG = {
    "name": "tiny",
    "source": "https://arxiv.org/abs/2602.17808",
    "in_channels": 3,
    "kernel": 3,
    "stride_cycle": [2, 1],
    "k_max": 4,
    "planner_platform": "EDGE_TPU_PLATFORM",
    "tenants": [
        {"name": "mobilenetv2", "profile": "mobilenetv2", "input_size": 16,
         "stage_channels": [4, 8, 8, 8, 8]},
        {"name": "mnasnet", "profile": "mnasnet", "input_size": 16,
         "stage_channels": [4, 8, 8, 8, 8, 8, 8]},
    ],
    "check": {"max_rel_err": 0.05},
}
TINY_MIX = {"name": "tiny_mix", "arrivals": "poisson", "rate_rps": 40.0,
            "zipf_s": 1.0, "pool_size": 4, "drain_timeout_s": 60}


def write_tree(root: Path, config=TINY_CONFIG, mix=TINY_MIX,
               workload="tiny.load") -> Path:
    """A benchmark tree under ``root`` holding one cell, with the
    repository's metric readers and model families."""
    (root / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "mixes").mkdir(parents=True, exist_ok=True)
    for d in ("metrics", "families"):
        shutil.copytree(REPO / "bench" / d, root / "bench" / d,
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (root / "bench" / "mixes" / f"{mix['name']}.json").write_text(json.dumps(mix))
    def e2e_metric(name, unit):
        return {"name": name, "unit": unit, "better": "lower", "bound": 0.25,
                "source": "host_clock", "workloads": [workload]}

    def layer_metric(name, unit, source):
        return {"name": name, "unit": unit, "better": "lower", "source": source,
                "layer": "test", "moves": "p50_ms", "workloads": [workload]}

    e2e = [e2e_metric("p50_ms", "ms"), e2e_metric("p99_ms", "ms"),
           e2e_metric("setup_s", "s")]
    per_layer = [layer_metric("gen_late_p99_ms", "ms", "host_clock"),
                 layer_metric("cut_kb_per_req", "KB", "program_counter"),
                 layer_metric("device_idle_frac", "1", "device_trace")]
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": config["name"], "source": config["source"],
                     "file": f"bench/configs/{config['name']}.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": workload, "config": config["name"],
                       "traffic": mix["name"], "chips": 1, "why": "test"}],
        "end_to_end": e2e, "per_layer": per_layer,
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return write_tree(tmp_path)
