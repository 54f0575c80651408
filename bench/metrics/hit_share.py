"""Runtime caches: share (%) of window requests answered by the exact-LRU
or the session-filter tier (the runtime's per-request path, the same count
as ``RuntimeTelemetry.paths``). Moves ``goodput_rps``."""
import numpy as np


def read(run):
    paths = run.sys_.rt.done_path
    idx = np.flatnonzero(run.window_mask()).tolist()
    done = [paths[i] for i in idx if i in paths]
    if not done:
        return None
    hits = sum(p in ("hit_exact", "hit_session") for p in done)
    return 100.0 * hits / len(done)
