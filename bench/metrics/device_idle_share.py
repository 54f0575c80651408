"""Device: share (%) of the traced window in which no operation ran on
the chip, 1 minus the union of device-op intervals. Moves
``goodput_rps``."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not t.n_ops:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
