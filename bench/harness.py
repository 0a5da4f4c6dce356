"""One run of one benchmark cell, driven by the files that name it.

``BENCHMARK.json`` names the cell; the cell names a configuration file
(``configs[].file``), a traffic mix (``bench/mixes/<traffic>.json``) and
its metrics, each read by ``bench/metrics/<metric>.py`` (or, for a metric
split by a suffix such as ``prefix_roofline.overload``, by the reader of
the part before the first dot).  The configuration's ``family`` key names
its model family, ``bench/families/<family>.py``; a configuration without
the key is a conv chain (``conv_chain``).  Adding a cell, a mix, a metric
or a configuration of a new family adds files and entries and edits none.

A family module provides, and the harness knows nothing else of the
model:

- ``make_data(config, pool_size, seed, device) -> (params, pools)``: the
  weights and each tenant's inputs, drawn from the seed.  ``pools[m]`` is
  a sequence that the schedule's ``item`` indexes, each element what
  ``ServingEngine.submit`` takes; the family decides where weights are
  drawn and held.
- ``build_models(config, params, control)``: the tenants as the program
  builds them, a list of ``ExecutableModel``; under ``control``, one of
  the module's ``CONTROLS``, the reference at that precision instead.
- ``make_plan(config, rates)``: the program's ``Plan`` at those rates.
- ``references(config, host_params, pools)``: ``refs[m][item]``, from a
  plain reference that imports nothing of the program.
- ``stage_costs(config, tenant)``: a ``bench.roofline.StageCost`` per
  stage.

A run makes the data, plans the tenants at the mix's offered rates, warms
every stage program up through ``ServingEngine`` on one input of each
shape in a tenant's pool, then offers the seeded schedule open loop for
the window from this one thread, drains, and checks every output against
the family's reference.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import arrivals, reference, roofline, stats, tracing
from bench.compile_clock import CompileClock

MIXES_DIR = "bench/mixes"
METRICS_DIR = "bench/metrics"
FAMILIES_DIR = "bench/families"
DEFAULT_FAMILY = "conv_chain"
# The tree this file belongs to.
ROOT = Path(__file__).resolve().parents[1]
# The profiler records this much of the window, from its start: a whole
# window's trace at these rates is hundreds of MB and takes longer to read
# than a run may last.
TRACE_S = 3.0
LEAD_S = 0.02          # from the end of set-up to the first due request


class NoChip(RuntimeError):
    """The process has no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]     # this cell's entries of each list
    per_layer: list[dict]


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} does not exist")
    with open(path) as f:
        return json.load(f)


def reader_path(root: Path, metric: str) -> Path:
    """The reader of ``metric``: ``<metric>.py``, else the reader of the
    part of the name before its first dot."""
    for stem in (metric, metric.split(".", 1)[0]):
        path = root / METRICS_DIR / f"{stem}.py"
        if path.is_file():
            return path
    raise FileNotFoundError(f"no reader {root / METRICS_DIR}/{metric}.py")


def family_path(root: Path, config: dict) -> Path:
    """The module of ``config``'s model family."""
    path = root / FAMILIES_DIR / f"{config.get('family', DEFAULT_FAMILY)}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no family module {path}")
    return path


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` and its files."""
    spec = _read_json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in {root / 'BENCHMARK.json'}")
    cfg = {c["name"]: c for c in spec["configs"]}.get(wl["config"])
    if cfg is None:
        raise KeyError(f"workload {workload!r} names no configuration "
                       f"{wl['config']!r}")
    mine = {kind: [m for m in spec[kind]
                   if workload in m.get("workloads", [workload])]
            for kind in ("end_to_end", "per_layer")}
    for m in mine["end_to_end"] + mine["per_layer"]:
        reader_path(root, m["name"])
    config = _read_json(root / cfg["file"])
    family_path(root, config)
    return Cell(name=workload, chips=int(wl["chips"]), config=config,
                mix=_read_json(root / MIXES_DIR / f"{wl['traffic']}.json"),
                **mine)


@functools.lru_cache(maxsize=None)
def _module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(f"{prefix}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(path: Path):
    return _module(path, "bench_metric").read


def family(root: Path, config: dict):
    """The loaded module of ``config``'s model family under ``root``."""
    return _module(family_path(root, config), "bench_family")


# The family's set-up steps for a caller that holds a configuration but no
# tree, the family being this tree's.  Their one caller is
# tests/test_engine_tracing.py; they go once it calls ``family`` instead.
def make_data(config: dict, pool_size: int, seed: int, device):
    return family(ROOT, config).make_data(config, pool_size, seed, device)


def build_models(config: dict, params, control: str | None):
    return family(ROOT, config).build_models(config, params, control)


def make_plan(config: dict, rates):
    return family(ROOT, config).make_plan(config, rates)


# -- set-up --------------------------------------------------------------------
def warm_up(engine, pools, timeout: float) -> None:
    """The first input of each shape in every tenant's pool once through the
    engine: each stage program of the plan compiles (or loads from the
    cache) for every shape the window will send, on the device that runs
    it."""
    for m, pool in enumerate(pools):
        seen = set()
        for x in pool:
            kind = (np.shape(x), np.result_type(x))
            if kind not in seen:
                seen.add(kind)
                engine.submit(m, x)
    bad = [c for c in engine.drain(timeout) if not c.ok]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0].error!r}")


# -- the run -------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read this."""

    plan: object
    seconds: float
    setup_s: float
    tenant: np.ndarray          # per scheduled request
    due: np.ndarray             # seconds after the window opened
    submit: np.ndarray          # engine's submit time, same origin; nan if none
    done: np.ndarray            # completion, same origin; inf if failed/missing
    costs: list                 # per tenant, a StageCost per stage
    peak: dict | None           # chip peaks; None off the chip
    traced_s: float = 0.0       # the traced interval is [0, traced_s)
    events: tracing.Events | None = None
    reduced: tracing.Reduced | None = None
    stage_execs: list | None = None   # (tenant, stage, start_ns, end_ns)

    @property
    def latency_s(self) -> np.ndarray:
        return self.done - self.due

    def traced_requests(self) -> np.ndarray:
        """Mask of the requests due inside the traced interval."""
        return self.due < self.traced_s

    def partition(self, m: int) -> int:
        return int(self.plan.partition[m])

    def n_stages(self, m: int) -> int:
        return len(self.costs[m])


def stage_executions(ev: tracing.Events, tenant_order: np.ndarray, plan):
    """Attribute the device's program executions to (tenant, stage).

    The accelerator worker runs prefixes one request at a time in submit
    order, each prefix's stages in order, and nothing else runs on the
    device once warm-up is over.  So the i-th module in the trace is a
    known stage of a known request.  Every module of one (tenant, stage)
    must carry the same program name; ``None`` where they do not.
    """
    seq = [(int(m), s) for m in tenant_order for s in range(plan.partition[m])]
    names, out = {}, []
    lo, hi = ev.window
    for (m, s), (start, end, name) in zip(seq, ev.modules):
        if names.setdefault((m, s), name) != name:
            return None
        if lo <= start < hi:
            out.append((m, s, start, end))
    if len(ev.modules) > len(seq):
        return None
    return out


def _match_records(records, sched, t0: float):
    """Per scheduled request, its record: within a tenant, records in
    submit order are that tenant's requests in schedule order."""
    n = len(sched)
    submit = np.full(n, np.nan)
    done = np.full(n, np.inf)
    outputs = [None] * n
    errored = 0
    for m in np.unique(sched.tenant):
        idx = np.flatnonzero(sched.tenant == m)
        mine = sorted((r for r in records if r.model_idx == m),
                      key=lambda r: r.submit_time)
        for k, r in zip(idx, mine):
            submit[k] = r.submit_time - t0
            if r.ok:
                done[k] = r.done_time - t0
                outputs[k] = r.output
            else:
                errored += 1
    return submit, done, outputs, errored


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float | None = None,
             require_tpu: bool = True, control: str | None = None,
             rate: float | None = None, log=print) -> dict:
    """One run; returns the result line as a dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(root, workload)
    fam = family(root, cell.config)
    if control is not None and control not in fam.CONTROLS:
        raise ValueError(f"{family_path(root, cell.config).stem} has no "
                         f"control {control!r}; it has {fam.CONTROLS}")
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX found {dev.platform} "
                     f"({dev.device_kind}); it does not fall back to the CPU")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {workload} needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    peak = roofline.peaks(dev.device_kind) if dev.platform == "tpu" else None

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.engine import ServingEngine

    clock = CompileClock()
    log(f"compile cache: {enable_compile_cache()}")
    cfg, mix = cell.config, dict(cell.mix)
    if rate is not None:
        mix["rate_rps"] = rate
    n_t = len(cfg["tenants"])
    sched = arrivals.schedule(mix, n_t, seconds, seed)
    rates = float(mix["rate_rps"]) * arrivals.zipf_shares(n_t, float(mix["zipf_s"]))

    def phase(name):
        log(f"[{time.perf_counter() - t_start:8.3f} s] {name} (compile "
            f"{clock.total:.3f} s, {clock.events} events, "
            f"{clock.cache_hits} cache hits)")

    phase("start")
    params, pools = fam.make_data(cfg, int(mix["pool_size"]), seed, dev)
    jax.block_until_ready((params, pools))
    phase("weights and inputs made")
    host_pools = jax.device_get(pools)
    del pools
    phase("inputs on the host")
    plan = fam.make_plan(cfg, rates)
    log(f"plan: partition={plan.partition} cores={plan.cores} at "
        f"{[round(float(r), 3) for r in rates]} req/s")
    engine = ServingEngine(fam.build_models(cfg, params, control), plan,
                           k_max=int(cfg["k_max"]))
    drain_s = float(mix["drain_timeout_s"])
    phase("engine built")
    try:
        warm_up(engine, host_pools, drain_s)
        phase("warm-up pass 1")
        warm_events = clock.events
        warm_up(engine, host_pools, drain_s)
        phase("warm-up pass 2")
        if clock.events != warm_events:
            log("warning: a second warm-up pass compiled again")
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        events0 = clock.events
        t0 = time.perf_counter() + LEAD_S
        setup_s = t0 - t_start
        traced_s = min(TRACE_S, seconds) if trace else 0.0
        mark = None
        if trace:
            time.sleep(max(0.0, t0 - time.perf_counter()))
            mark = jax.profiler.TraceAnnotation(tracing.MARK)
            mark.__enter__()
        submit = engine.submit
        for k in range(len(sched)):
            due = t0 + sched.due[k]
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if mark is not None and sched.due[k] >= traced_s:
                mark.__exit__(None, None, None)
                mark = None
                jax.profiler.stop_trace()
            m = int(sched.tenant[k])
            submit(m, host_pools[m][int(sched.item[k])])
        wait = t0 + seconds - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if mark is not None:
            mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
        t_close = time.perf_counter()
        try:
            records = engine.drain(drain_s)
        except TimeoutError:
            log(f"engine did not drain within {drain_s} s")
            records = []
        compiles_in_window = clock.events - events0
        memory = dev.memory_stats() or {}
    finally:
        engine.shutdown()
    submit_t, done, outputs, errored = _match_records(records, sched, t0)
    missing = len(sched) - len(records)
    outputs = [None if o is None else np.asarray(o) for o in outputs]
    host_params = jax.device_get(params)
    del params, engine, records

    run = Run(plan=plan, seconds=seconds, setup_s=setup_s,
              tenant=sched.tenant, due=sched.due, submit=submit_t, done=done,
              costs=[fam.stage_costs(cfg, t) for t in cfg["tenants"]],
              peak=peak, traced_s=traced_s)
    if trace:
        run.events = tracing.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.reduced = tracing.reduce(run.events)
        prefix = sched.tenant[[plan.partition[m] > 0 for m in sched.tenant]]
        run.stage_execs = stage_executions(run.events, prefix, plan)
        if run.stage_execs is None:
            log("warning: device programs could not be attributed to stages")

    # The check, after the window and with the program's state freed.
    t_check = time.perf_counter()
    refs = fam.references(cfg, host_params, host_pools)
    errs = [reference.relative_error(o, refs[sched.tenant[k]][sched.item[k]])
            for k, o in enumerate(outputs) if o is not None]
    limit = float(cfg["check"]["max_rel_err"])
    max_err = max(errs) if errs else math.inf
    check = {
        "max_rel_err": {"value": max_err, "limit": limit},
        "errored": {"value": errored, "limit": 0},
        "missing": {"value": missing, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in check.values())
    check_s = time.perf_counter() - t_check
    log(f"set-up {setup_s:.3f} s, drain {t_check - t_close:.3f} s after the "
        f"window, check {check_s:.3f} s")
    late = submit_t - sched.due
    worst = np.argsort(-np.nan_to_num(late, nan=-np.inf))[:5]
    log("latest submits (due s, late ms): " + ", ".join(
        f"{sched.due[k]:.3f}/{1e3 * late[k]:.1f}" for k in worst))

    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = _reader(reader_path(root, m["name"]))(run)
        if value is None or not math.isfinite(value):
            log(f"metric {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(memory.get("peak_bytes_in_use", 0))}
    result = {"correct": bool(correct), "attempted": len(sched),
              "failed": errored + missing, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.reduced.busy_s
        device["window_s"] = run.reduced.window_s
        result["breakdown"] = {"device_ops": run.reduced.device_ops,
                               "idle_gaps": run.reduced.idle_gaps}
    result["window"] = {
        "completed_in_window": int(np.sum(done <= seconds)),
        "backlog_mid": int(np.sum(sched.due <= seconds / 2)
                           - np.sum(done <= seconds / 2)),
        "backlog_end": int(len(sched) - np.sum(done <= seconds)),
        "gen_late_p99_ms": 1e3 * stats.nearest_rank(
            (submit_t - sched.due)[np.isfinite(submit_t)], 99),
        "compiles_in_window": compiles_in_window,
        "drain_s": t_check - t_close, "check_s": check_s,
        "plan": {"partition": list(plan.partition), "cores": list(plan.cores)},
        "rate_rps": float(mix["rate_rps"]), "seed": seed, "control": control,
    }
    result["check"] = check
    return result


def main(args, t_start: float, root: Path) -> int:
    """Entry of ``bench/run.py``: prints the result line, or exits 2 with
    no result where there is no chip."""
    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        result = run_cell(root, args.workload, args.seed, float(args.seconds),
                          bool(args.trace), t_start=t_start, control=args.control,
                          rate=args.rate, log=log)
    except NoChip as exc:
        log(f"bench: {exc}")
        return 2
    for name, c in result["check"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(result))
    return 0
