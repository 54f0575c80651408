"""Engines on the device: device busy time (ms) inside the traced
``complete`` spans, per dispatch. Beside ``service_ms`` it splits service
into host and device. Moves ``goodput_rps``."""


def read(run):
    t = run.trace
    if t is None or not t.n_complete or not t.n_ops:
        return None
    return 1e3 * t.busy_in_complete_s / t.n_complete
