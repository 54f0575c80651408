"""Micro-batcher: mean real-clock wait (ms) of a miss, from its enqueue to
the start of the dispatch that takes it (the runtime's ``queue.wait``
records), over the misses dispatched in the stretch read. Moves
``goodput_rps``."""
import numpy as np

from bench import stages


def read(run):
    lg = stages.log(run)
    if lg is None:
        return None
    waits = [t1 - t0 for _, t0, t1, _ in lg.waits
             if stages.in_stretch(run, t1)]
    return float(np.mean(waits)) / 1e6 if waits else None
