#!/usr/bin/env python3
"""Show that the check of a cell fails a server that breaks its guarantee.

    python3 bench/control.py --workload aol.first-term \\
        --seeds 2147480101,2147480102,2147480103 --seconds 10

Sets the cell up once, as ``run_cell.py`` does, but with the control in the
program's place under the timed path: a server whose corpus lacks its
lowest-scored eighth, the shortcut of keeping only the popular completions,
which breaks the exact top-k that the configuration states. Then, for each
seed, it serves the cell's traffic at the cell's own load for a window of
``--seconds``, runs the cell's check, and prints one JSON line with
``correct`` (which has to read false) and the numbers compared, each beside
its limit. Not part of the benchmark's own runs. Exits nonzero and prints
nothing when JAX finds no TPU.
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
PRUNED = 1 / 8     # share of the corpus, lowest scores, that the control drops
INF = 2**31 - 1


def keep_rows(ref) -> int:
    """Completions the control keeps: the best-scored seven eighths."""
    return len(ref) - int(len(ref) * PRUNED)


def pruned(fe, ref) -> None:
    """Put the control in ``fe``'s place: every answer is the reference's
    over the pruned corpus. Docids are ranks by score, so the pruned eighth
    is the docids from ``keep_rows`` on; an exact top-k that holds one of
    them holds every later match too, so dropping them from the exact answer
    gives the pruned corpus's top-k (the CPU test compares the two)."""
    keep = keep_rows(ref)
    complete = fe.complete

    def control(*args, **kwargs):
        out = np.array(complete(*args, **kwargs))
        out[out >= keep] = INF
        return out

    fe.complete = control


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.load_cell(args.workload)
    try:
        harness.tpu_devices(cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    sys_ = harness.build_system(cell, wrap_frontend=pruned)
    harness.offer(sys_, cell.traffic, args.seconds, seeds[0])
    harness.warm_variants(sys_)
    harness.settle()
    harness.log(f"set-up {time.perf_counter() - T_START:.1f}s")
    for seed in seeds:
        win = harness.rerun(sys_, cell.traffic, args.seconds, seed)
        chk = harness.check(sys_, win, seed)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": harness.is_correct(chk), "strata": chk["strata"],
            "checks": harness.checks_line(chk)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
