import os
# The dry run compiles on forced host devices, never on an attached chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes and capture memory/cost/collective statistics.

MUST be run as a standalone process (the XLA flag above is set before any
jax import and locks the device count).  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Results (memory_analysis, cost_analysis, collective bytes parsed from the
compiled HLO) are appended as JSON lines under experiments/dryrun/.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHS, INPUT_SHAPES
from repro.launch.mesh import auto_mesh, make_production_mesh
from repro.launch.steps import build_step
from repro.roofline.analysis import analyze_compiled

# §Perf knobs applied under --opt.  Per-arch overrides come from the
# hillclimb iterations in EXPERIMENTS.md §Perf.
OPT_DEFAULT = dict(use_chunked_scan=True)
OPT_OVERRIDES: dict[str, dict] = {
    # 7.5B params: weight all-gather (ZeRO-3) is ~50x cheaper than
    # tensor-parallel activation all-reduce at batch 1/chip.
    "rwkv6-7b": dict(use_chunked_scan=True, parallelism="fsdp"),
    # d_inner=3200 is not 256-divisible, so ZeRO sharding degenerates for
    # half the tensors; TP + chunked SSD is the best fitting config.
    "hymba-1.5b": dict(use_chunked_scan=True),
    # 8 experts cannot map onto a 16-wide axis; refactor the logical mesh to
    # 32x8 so experts are expert-parallel on 'model' (d_model over 'data').
    "grok-1-314b": dict(use_chunked_scan=True,
                         mesh=(32, 8), capacity_factor=1.0),
}


def run_one(
    arch_name: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    optimized: bool = False,
    out_dir: str = "experiments/dryrun",
    verbose: bool = True,
) -> dict:
    import dataclasses as _dc

    cfg = ARCHS[arch_name]
    mesh_shape: tuple | None = None
    if optimized:
        ov = dict(OPT_OVERRIDES.get(arch_name, OPT_DEFAULT))
        mesh_shape = ov.pop("mesh", None)
        cfg = _dc.replace(cfg, **ov)
    shape = INPUT_SHAPES[shape_name]
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    record: dict = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": mesh_tag,
        "variant": "optimized" if optimized else "baseline",
        "status": "",
    }
    if not cfg.supports_shape(shape_name):
        record["status"] = "skipped"
        record["reason"] = (
            "full-attention arch: long_500k decode requires sub-quadratic "
            "attention (see DESIGN.md Sec. 4)"
        )
        _append(out_dir, record)
        if verbose:
            print(f"[skip] {arch_name} x {shape_name}: full attention")
        return record

    if mesh_shape is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    else:
        if multi_pod:
            mesh = auto_mesh((2, *mesh_shape), ("pod", "data", "model"))
        else:
            mesh = auto_mesh(mesh_shape, ("data", "model"))
        record["mesh_factorization"] = list(mesh_shape)
    bundle = build_step(cfg, shape, mesh)
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                bundle.fn,
                in_shardings=bundle.in_shardings,
                out_shardings=bundle.out_shardings,
                donate_argnums=bundle.donate_argnums,
            )
            lowered = jitted.lower(*bundle.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        record["status"] = "ok"
        record["lower_s"] = round(t_lower, 1)
        record["compile_s"] = round(t_compile, 1)
        record["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
        if record["memory"]["peak_bytes"] is None:
            # The CPU backend's memory analysis reports component sizes but
            # no peak; approximate it as args + outputs + temps (an upper
            # bound on simultaneously-live buffers) and say so.
            parts = [
                record["memory"][key]
                for key in ("argument_bytes", "output_bytes", "temp_bytes")
            ]
            if all(p is not None for p in parts):
                record["memory"]["peak_bytes"] = sum(parts)
                record["memory"]["peak_is_estimate"] = True
        record.update(analyze_compiled(cfg, shape, mesh, compiled))
        if verbose:
            gb = (record["memory"]["peak_bytes"] or 0) / 2**30
            print(
                f"[ok]   {arch_name} x {shape_name} ({mesh_tag}): "
                f"peak={gb:.2f} GiB/device, "
                f"compute={record['roofline']['compute_s']:.4f}s "
                f"memory={record['roofline']['memory_s']:.4f}s "
                f"collective={record['roofline']['collective_s']:.4f}s "
                f"-> {record['roofline']['bottleneck']} "
                f"[lower {record['lower_s']}s compile {record['compile_s']}s]"
            )
    except Exception as e:  # noqa: BLE001 -- record the failure, keep sweeping
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[FAIL] {arch_name} x {shape_name}: {record['error']}")
    _append(out_dir, record)
    return record


def _append(out_dir: str, record: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    suffix = "_opt" if record.get("variant") == "optimized" else ""
    fname = os.path.join(out_dir, f"dryrun_{record['mesh']}{suffix}.jsonl")
    with open(fname, "a") as f:
        f.write(json.dumps(record) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one architecture id")
    ap.add_argument("--shape", default=None, help="one input-shape id")
    ap.add_argument("--all", action="store_true", help="sweep all pairs")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh")
    ap.add_argument("--opt", action="store_true", help="apply §Perf knobs")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    args = ap.parse_args()

    assert jax.device_count() == 512, (
        f"dry-run expects 512 forced host devices, got {jax.device_count()}"
    )

    pairs: list[tuple[str, str]]
    if args.all:
        pairs = [(a, s) for a in ARCHS for s in INPUT_SHAPES]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        pairs = [(args.arch, args.shape)]

    n_ok = n_skip = n_fail = 0
    for a, s in pairs:
        rec = run_one(
            a, s,
            multi_pod=args.multi_pod,
            optimized=args.opt,
            out_dir=args.out_dir,
        )
        n_ok += rec["status"] == "ok"
        n_skip += rec["status"] == "skipped"
        n_fail += rec["status"] == "error"
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
