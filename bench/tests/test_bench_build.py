"""The host index build's reader, ``index_build_s``: on a CPU traced run
of the tiny cell, and on a program that records no build stages."""
import time
from types import SimpleNamespace

import pytest

from bench import harness


def test_index_build_s_reads_the_tiny_cells_build(tiny_root, tmp_path):
    t_start = time.perf_counter()
    result, sys_, win, _ = harness.execute(
        "tiny.sessions", 2147483659, 1.0, True, t_start=t_start,
        root=tiny_root, require_tpu=False, trace_dir=str(tmp_path / "trace"))
    assert result["correct"] is True
    got = result["metrics"]["index_build_s"]
    assert got["unit"] == "s"
    setup_s = win.epoch + win.start - t_start
    assert 0 < got["value"] <= setup_s


def _bare_run():
    return SimpleNamespace(win=SimpleNamespace(epoch=time.perf_counter()))


@pytest.mark.parametrize("program", ["no_accessor", "no_records",
                                     "records_after_the_epoch"])
def test_index_build_s_reads_none_without_build_stages(program, monkeypatch):
    """As on a program that has no build stages (the accessor is gone), or
    records none before the window."""
    from repro.obs import tracing
    run = _bare_run()
    late = int(run.win.epoch * 1e9) + 1
    if program == "no_accessor":
        monkeypatch.delattr(tracing, "build_stages")
    elif program == "no_records":
        monkeypatch.setattr(tracing, "build_stages", lambda: [])
    else:
        monkeypatch.setattr(tracing, "build_stages", lambda: [
            ("build.corpus", late, late + 5, {"path": "bulk"})])
    assert harness.load_reader("index_build_s")(run) is None


def test_index_build_s_reads_the_newest_build(monkeypatch):
    from repro.obs import tracing
    run = _bare_run()
    e = int(run.win.epoch * 1e9)
    recs = [("build.corpus", e - 90, e - 80, {"path": "loop"}),
            ("build.rmq", e - 80, e - 70, {}),
            ("build.corpus", e - 50, e - 40, {"path": "bulk"}),
            ("build.pack", e - 40, e - 20, {}),
            ("build.rmq", e - 20, e - 10, {}),
            ("build.corpus", e + 10, e + 20, {"path": "bulk"})]
    monkeypatch.setattr(tracing, "build_stages", lambda: recs)
    assert harness.load_reader("index_build_s")(run) == pytest.approx(40e-9)
