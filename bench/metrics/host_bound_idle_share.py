"""Serve host path: share (%) of the traced window in which the chip was
idle while the host ran the program's serving stages (its ``runtime.*``,
``frontend.*`` and ``engine.*`` annotations), by exact intersection of
their union with the device's busy intervals. The rest of
``device_idle_share`` is idle for want of arrivals or in the harness's own
loop. Moves ``goodput_rps``."""
from bench import stages, traces


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not t.n_ops:
        return None
    spans = stages.program_spans(run)
    if not spans:
        return None
    idle = sum((e - s) - traces.overlap(t.busy, s, e) for s, e in spans)
    return 100.0 * idle / (t.window[1] - t.window[0])
