"""Runtime caches: mean ``runtime.lookup`` time (us) of the window
requests answered at arrival (trivial, exact-LRU or session-filter hits),
from the runtime's per-request records. Moves ``goodput_rps``."""
import numpy as np

from bench import stages


def read(run):
    lg = stages.log(run)
    if lg is None:
        return None
    paths = run.sys_.rt.done_path
    want = set(np.flatnonzero(run.window_mask()).tolist())
    ns = [dt for i, dt in lg.lookups
          if i in want and paths.get(i, "miss") != "miss"]
    return float(np.mean(ns)) / 1e3 if ns else None
