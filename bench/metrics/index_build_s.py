"""Host index build: seconds from the first start to the last end of the
newest index build's stages (``repro.obs.tracing.build_stages``: the
``build.*`` stages that tile ``build_qac_index``, a build beginning at its
``build.corpus``) recorded before the window's epoch; None when the program
records none. Moves ``setup_s``."""


def read(run):
    try:
        from repro.obs.tracing import build_stages
    except ImportError:
        return None
    epoch_ns = run.win.epoch * 1e9
    recs = [r for r in build_stages() if r[2] <= epoch_ns]
    starts = [t0 for name, t0, _, _ in recs if name == "build.corpus"]
    if not starts:
        return None
    t0 = max(starts)
    return (max(t1 for _, s, t1, _ in recs if s >= t0) - t0) / 1e9
