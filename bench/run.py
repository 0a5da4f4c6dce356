#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload cnn3.poisson_load --seed 7 \
        --seconds 51 --trace 0

Run from the root of a checkout with ``JAX_PLATFORMS`` unset, so that the
TPU and the host CPU backend both exist.  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``; ``breakdown`` with ``--trace 1``;
then ``window`` and ``check``); the last lines of standard error give
each number compared with its limit.  ``--rate`` (offered requests per
second) and ``--control`` (serve the reference at a lower precision) are
for the knee sweep and the control, never for a measured cell.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", default=None,
                    help="one of the configuration's family's CONTROLS")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    # The compile cache lives in the checkout, at a fixed path, whatever
    # the environment says; the program takes the directory from here.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    return harness.main(args, t_start=T_START, root=ROOT)


if __name__ == "__main__":
    sys.exit(main())
