#!/usr/bin/env python3
"""Run one cell several times, one process per run, and summarise.

    python3 bench/repeat.py --workload cnn3.poisson_load --seeds 11,12,13 \
        --seconds 51 [--sets 2] [--rates 300,400] [--trace 1] \
        [--control fp8] [--out runs.jsonl]

Each run is ``bench/run.py`` in a child process; this process never
touches JAX, so each child has the chip to itself.  With ``--rates`` it
runs every seed at every offered rate (the knee sweep); with ``--sets N``
it runs the seed list N times over.  Every result line is appended to
``--out``; the summary gives, per rate and set, each metric's median and
its quartile spread (``statistics.quantiles(n=4)``, as a share of the
median).
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.stats import quartile_spread  # noqa: E402


def one(args, seed: int, rate: float | None) -> dict:
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"),
           "--workload", args.workload, "--seed", str(seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace)]
    if rate is not None:
        cmd += ["--rate", str(rate)]
    if args.control:
        cmd += ["--control", args.control]
    t = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=args.timeout)
    rec = {"seed": seed, "rate": rate, "rc": p.returncode,
           "wall_s": time.perf_counter() - t}
    if args.out:
        with open(args.out + ".stderr", "a") as f:
            f.write(f"=== {' '.join(cmd[1:])} rc={p.returncode}\n{p.stderr}\n")
    lines = p.stdout.strip().splitlines()
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        rec["stderr_tail"] = p.stderr[-3000:]
    return rec


def show(rec: dict) -> str:
    r = rec.get("result")
    if r is None:
        return f"seed={rec['seed']} rate={rec['rate']} rc={rec['rc']} NO RESULT\n{rec.get('stderr_tail', '')}"
    m = " ".join(f"{k}={v['value']:.6g}" for k, v in r["metrics"].items())
    c = " ".join(f"{k}={v['value']:.4g}/{v['limit']}" for k, v in r["check"].items())
    w = r.get("window", {})
    dev = r["device"]
    extra = (f" busy_s={dev['busy_s']:.4g} window_s={dev['window_s']:.4g}"
             if "busy_s" in dev else "")
    return (f"seed={rec['seed']} rate={w.get('rate_rps')} rc={rec['rc']} "
            f"wall={rec['wall_s']:.1f}s correct={r['correct']} "
            f"attempted={r['attempted']} failed={r['failed']} {m} | {c} | "
            f"served_in_window={w.get('completed_in_window')} "
            f"backlog_mid={w.get('backlog_mid')} backlog_end={w.get('backlog_end')} "
            f"gen_late_p99_ms={w.get('gen_late_p99_ms')} "
            f"compiles_in_window={w.get('compiles_in_window')} plan={w.get('plan')} "
            f"drain_s={w.get('drain_s')} check_s={w.get('check_s')} "
            f"mem={dev.get('memory_peak_bytes')}{extra}")


def summarise(label: str, recs: list[dict]) -> None:
    results = [r["result"] for r in recs if "result" in r]
    names = sorted({k for r in results for k in r["metrics"]})
    print(f"== {label}: {len(results)} runs, "
          f"{sum(r['correct'] for r in results)} correct")
    for name in names:
        vals = [r["metrics"][name]["value"] for r in results if name in r["metrics"]]
        line = f"   {name}: median={statistics.median(vals):.6g}"
        if len(vals) >= 3:
            line += f" spread={quartile_spread(vals):.4f}"
        print(line + f" values={[float(f'{v:.6g}') for v in vals]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--control", default=None)
    ap.add_argument("--timeout", type=float, default=1200)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    out = open(args.out, "a") if args.out else None
    try:
        for rate in rates:
            for k in range(args.sets):
                recs = []
                for seed in seeds:
                    rec = one(args, seed, rate)
                    recs.append(rec)
                    print(show(rec), flush=True)
                    if out:
                        out.write(json.dumps({"workload": args.workload, "set": k,
                                              **rec}) + "\n")
                        out.flush()
                summarise(f"{args.workload} rate={rate} set={k}", recs)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
