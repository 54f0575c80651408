"""Frontend and engines: mean host wall time (ms) of a
``QACFrontend.complete`` call that started in the stretch read, from the
harness's own span around the public method. Moves ``goodput_rps``."""
import numpy as np


def read(run):
    ep, (b0, b1) = run.win.epoch, run.bounds
    ms = [(t1 - t0) * 1e3 for t0, t1, _, _ in run.sys_.spans
          if b0 <= t0 - ep < b1]
    return float(np.mean(ms)) if ms else None
