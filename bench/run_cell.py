#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run_cell.py --workload aol.first-term --seed 7 \\
        --seconds 30 --trace 0

Generates the cell's corpus from the seed, builds it with the program's
``build_qac_index``, warms the cell's own jit variants and caches, offers
the cell's traffic on the real clock for ``--seconds``, checks a seeded
sample of the answers against the plain reference, and prints one JSON
object (``--trace 0``: the end-to-end metrics; ``--trace 1``: the per-layer
metrics, read from a profiler trace of a window cut to the traffic file's
``trace_seconds``). Exits nonzero and prints no result when JAX finds no
TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        from bench import harness
    except ImportError as e:
        print(f"run_cell: cannot import the benchmark ({e})", file=sys.stderr)
        return 2
    try:
        harness.log(f"compile cache: {harness.enable_compile_cache()}")
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"run_cell: {e}; nothing was run", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
