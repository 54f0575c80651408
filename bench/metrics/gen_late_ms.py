"""Load generator: p99 of how late each window request was submitted
(submit time minus due time, ms). Moves ``goodput_rps``: a late generator
delays requests that a faster server would not."""
import numpy as np


def read(run):
    m = run.window_mask()
    late = (run.win.submit[m] - run.sys_.due_s[m]) * 1e3
    late = late[~np.isnan(late)]
    return float(np.percentile(late, 99)) if late.size else None
