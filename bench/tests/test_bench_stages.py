"""The readers of the program's serving stages: on a CPU traced run of the
tiny cell, on a small recorded chip trace, and on a program that keeps no
stages.

The chip trace is the tiny cell served on a TPU v5e by ``harness.execute``
with ``trace_seconds`` 0.3, kept to the traced window's device ops and the
host spans the readers use (``data/tiny_v5e_stages.xplane.pb.gz``); the
runtime's stage records from the same run, cut to the traced stretch, and
the seven metrics it reported are ``data/tiny_v5e_stages.records.json``.
"""
import gzip
import json
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, traces

DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "tiny_v5e_stages.xplane.pb.gz"
RECORDS = DATA / "tiny_v5e_stages.records.json"
PROGRAM_SIDE = ("queue_wait_ms", "dispatch_prep_ms", "device_wait_ms",
                "scatter_ms", "lookup_us", "gc_pause_ms")
ALL = PROGRAM_SIDE + ("host_bound_idle_share",)


def _read(name, run):
    return harness.load_reader(name)(run)


def test_traced_run_reports_the_program_stages(tiny_root, tmp_path):
    result, sys_, win, _ = harness.execute(
        "tiny.sessions", 1234567890123, 2.0, True,
        t_start=time.perf_counter(), root=tiny_root, require_tpu=False,
        trace_dir=str(tmp_path / "trace"))
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for name in PROGRAM_SIDE:
        assert name in got and got[name] >= 0, (name, got)
    assert got["lookup_us"] > 0 and got["device_wait_ms"] > 0
    # the CPU has no device plane: the chip's idle is not there to split
    assert "host_bound_idle_share" not in got
    # the per-dispatch parts add up to the dispatches they came from
    run = harness.RunContext(sys_=sys_, win=win, trace=None,
                             device_kind="cpu")
    from bench import stages
    whole = stages.per_dispatch_ms(run, lambda n: n == "runtime.dispatch")
    parts = (got["dispatch_prep_ms"] + got["device_wait_ms"]
             + got["scatter_ms"])
    assert parts == pytest.approx(whole, rel=0.02)


@pytest.fixture(scope="module")
def chip(tmp_path_factory):
    """A run context over the recorded chip trace and stage records."""
    rec = json.loads(RECORDS.read_text())
    d = tmp_path_factory.mktemp("chip") / "plugins" / "profile" / "0"
    d.mkdir(parents=True)
    with gzip.open(TRACE, "rb") as f:
        (d / "t.xplane.pb").write_bytes(f.read())
    mask = np.zeros(rec["n_requests"], bool)
    mask[rec["in_window"]] = True
    log = SimpleNamespace(spans=rec["spans"], waits=rec["waits"],
                          lookups=rec["lookups"])
    rt = SimpleNamespace(
        telemetry=SimpleNamespace(stages=log),
        done_path={int(i): p for i, p in rec["done_path"].items()})
    run = SimpleNamespace(
        sys_=SimpleNamespace(rt=rt),
        win=SimpleNamespace(epoch=rec["epoch"],
                            trace_dir=str(d.parents[2])),
        bounds=tuple(rec["bounds"]), window_mask=lambda: mask,
        trace=traces.reduce_file(str(TRACE)), device_kind="TPU v5 lite")
    return run, rec


def test_stage_readers_on_a_recorded_chip_trace(chip, monkeypatch):
    from repro.obs import tracing
    run, rec = chip
    monkeypatch.setattr(tracing, "gc_pauses",
                        lambda: [tuple(g) for g in rec["gc_pauses"]])
    got = {name: _read(name, run) for name in ALL}
    for name in ALL:
        # the values the chip run reported, from these records and trace
        assert got[name] is not None, name
        assert got[name] == pytest.approx(rec["metrics"][name], rel=1e-9)
    assert 0 < got["host_bound_idle_share"] < 100


def test_host_bound_idle_lies_within_device_idle(chip):
    run, _ = chip
    host_bound = _read("host_bound_idle_share", run)
    idle = _read("device_idle_share", run)
    assert 0 < host_bound <= idle


def test_the_chip_trace_names_each_engine_module():
    from jax.profiler import ProfileData
    with gzip.open(TRACE, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    modules = {ev.name.split("(")[0]
               for plane in pd.planes
               if plane.name.startswith(traces.DEVICE_PLANE)
               for line in plane.lines if line.name == "XLA Modules"
               for ev in line.events}
    assert {"jit_qac_single", "jit_qac_multi"} <= modules, modules
    assert not any(m.startswith(("jit__single", "jit__lambda"))
                   for m in modules)


def test_readers_report_nothing_for_a_program_without_stages(
        chip, monkeypatch, tmp_path):
    """An older program, as the benchmark meets it on the parent commit:
    no stage log, no collector clock, and a chip trace with no stage
    annotations (the fixture recorded before the program had them)."""
    from repro.obs import tracing
    run, _ = chip
    old = DATA / "tiny_v5e.xplane.pb.gz"
    with gzip.open(old, "rb") as f:
        (tmp_path / "t.xplane.pb").write_bytes(f.read())
    bare = SimpleNamespace(
        sys_=SimpleNamespace(rt=SimpleNamespace(
            telemetry=SimpleNamespace(), done_path={})),
        win=SimpleNamespace(epoch=run.win.epoch, trace_dir=str(tmp_path)),
        bounds=run.bounds, window_mask=run.window_mask,
        trace=traces.reduce_file(str(old)), device_kind=run.device_kind)
    monkeypatch.delattr(tracing, "gc_pauses")
    for name in ALL:
        assert _read(name, bare) is None, name
