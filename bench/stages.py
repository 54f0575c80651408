"""The program's serving stages, as the per-layer readers use them.

Two sources, each on its own clock:

- the runtime's records (``RuntimeTelemetry.stages``; taxonomy in
  ``repro/obs/tracing.py``), in ``time.perf_counter_ns``, the clock of
  ``Window.epoch``, so a reader keeps those in the stretch it reads;
- the stage annotations the program makes while a profiler session is
  active, on the trace's host plane beside the device ops: what the chip
  did while the host served.

A program that records no stages gives None from both, and its readers
report nothing.
"""
from __future__ import annotations

import gzip

from bench import traces

PROGRAM = ("runtime.", "frontend.", "engine.")
_FILES: dict = {}


# -- the runtime's records ----------------------------------------------------
def log(run):
    """The runtime's stage log, or None when the program keeps none."""
    return getattr(run.sys_.rt.telemetry, "stages", None)


def in_stretch(run, t_ns: int) -> bool:
    """Did a ``perf_counter_ns`` reading fall inside the stretch read?"""
    b0, b1 = run.bounds
    return b0 <= t_ns * 1e-9 - run.win.epoch < b1


def per_dispatch_ms(run, pred):
    """Mean per dispatch (ms) of the stages whose name ``pred`` accepts,
    over the dispatches that started in the stretch read."""
    lg = log(run)
    if lg is None:
        return None
    ids = {d for name, t0, _, d, _ in lg.spans
           if name == "runtime.dispatch" and in_stretch(run, t0)}
    if not ids:
        return None
    total = sum(t1 - t0 for name, t0, t1, d, _ in lg.spans
                if d in ids and pred(name))
    return total / len(ids) / 1e6


# -- the annotations in the trace ---------------------------------------------
def read_file(path: str) -> list:
    """Every program stage annotation in a ``.xplane.pb`` (or its gzip):
    ``(start_ns, end_ns)`` on the trace's clock."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith(traces.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def program_spans(run):
    """The union of the program's stage annotations inside the traced
    window, merged (ns); None without a trace. Read once per file."""
    t, trace_dir = run.trace, run.win.trace_dir
    if t is None or not trace_dir:
        return None
    path = traces.xplane_file(trace_dir)
    if path not in _FILES:
        _FILES[path] = read_file(path)
    w0, w1 = t.window
    return traces.merge((max(s, w0), min(e, w1)) for s, e in _FILES[path]
                        if e > w0 and s < w1)
