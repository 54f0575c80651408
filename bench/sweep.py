#!/usr/bin/env python3
"""Find the highest rate a deployment sustains under a traffic mix: one
set-up, then a ladder of offered rates, each for one or more seeds. Not a
cell.

    python3 bench/sweep.py --config qac-aol --traffic first-term \\
        --seeds 11,12 --rates 2000,4000,8000 --seconds 30

The configuration and the traffic mix are found by name
(``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``), so a
rate can be found before its cell is in ``BENCHMARK.json``. Each rate
replaces the traffic file's ``request_rate`` (requests per second); the
caches start empty at each step and warm for the file's ``warm_seconds``.
Per step it prints the offered and answered requests per second, p50, p95
and p99 on the real clock, goodput, the longest call to the frontend, and
the backlog trend: the mean latency of the last third of the step's
requests over that of the first third (near 1 when the queue is steady,
growing when it is not). The chosen rate is then written into the traffic
file by hand. Exits nonzero and prints nothing when JAX finds no TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def step(harness, sys_, cell, rate, seconds, seed, drain_cap_s):
    traffic = dict(cell.traffic, request_rate=rate)
    n_spans = len(sys_.spans)
    win = harness.rerun(sys_, traffic, seconds, seed, drain_cap_s=drain_cap_s)
    lat = harness.latencies_ms(sys_, win)
    third = max(1, lat.size // 3)
    first, last = lat[:third], lat[-third:]
    calls = [t1 - t0 for t0, t1, _, _ in sys_.spans[n_spans:]]
    return {
        "request_rate": rate,
        "seed": seed,
        "offered_rps": lat.size / seconds,
        "answered_rps": float(np.isfinite(lat).sum()) / seconds,
        "p50_ms": harness.percentile(lat, 50),
        "p95_ms": harness.percentile(lat, 95),
        "p99_ms": harness.percentile(lat, 99),
        "goodput_rps": float((lat <= harness.SLA_MS).sum()) / seconds,
        "longest_call_ms": 1e3 * max(calls, default=0.0),
        "backlog_trend": float(np.mean(last) / max(np.mean(first), 1e-9)),
        "unanswered": int((~np.isfinite(lat)).sum()),
        "compiles": len(sys_.auditor.violations),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, each run at every rate")
    ap.add_argument("--rates", required=True,
                    help="comma-separated request rates (req/s), ascending")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--drain-cap", type=float, default=30.0)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.draft_cell(args.config, args.traffic)
    try:
        harness.tpu_devices(cell.chips)
    except harness.NoChip as e:
        print(f"sweep: {e}; nothing was run", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    seeds = [int(s) for s in args.seeds.split(",")]
    sys_ = harness.build_system(cell)
    harness.offer(sys_, dict(cell.traffic, request_rate=rates[-1]),
                  args.seconds, seeds[0])
    harness.warm_variants(sys_)
    harness.settle()
    harness.log(f"set-up {time.perf_counter() - T_START:.1f}s")
    for rate in rates:
        for seed in seeds:
            row = step(harness, sys_, cell, rate, args.seconds, seed,
                       args.drain_cap)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
