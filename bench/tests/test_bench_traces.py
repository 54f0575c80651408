"""The trace-to-metrics reduction on a small recorded trace: the tiny cell
served on a TPU v5e (``harness.execute`` with ``trace_seconds`` 0.15, which
the loop cut to 72 ms), kept to the traced window's device ops and the
harness's own host spans (``data/tiny_v5e.xplane.pb.gz``, 92 KB)."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, traces
from bench.kernels import rmq

TRACE = Path(__file__).resolve().parent / "data" / "tiny_v5e.xplane.pb.gz"


@pytest.fixture(scope="module")
def red():
    return traces.reduce_file(str(TRACE))


def test_window_busy_and_gaps_partition_the_window(red):
    # the numbers the reduction gave when the trace was recorded
    assert red.window_s == pytest.approx(0.07172832)
    assert red.busy_s == pytest.approx(0.001875224)
    assert (red.n_ops, red.n_complete) == (4097, 5)
    assert len(red.ops_matching(rmq.matches)) == 39
    idle = sum(e - s for s, e in red.gaps()) / 1e9
    assert idle + red.busy_s == pytest.approx(red.window_s, rel=1e-9)
    assert red.n_complete > 0
    assert 0 < red.busy_in_complete_s <= red.busy_s


def test_self_times_add_up_to_busy_time(red):
    # ops on one line only nest, so their self times tile the busy time
    total = sum(op.self_ns for op in red.ops) / 1e9
    assert total == pytest.approx(red.busy_s, rel=0.01)
    assert all(op.self_ns >= 0 for op in red.ops)


def test_breakdown_lists_ops_and_idle_by_host_activity(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert {k for k, _ in b["idle_gaps"]} <= {
        "generator", "complete", "submit", "tick", "wait"}
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)


def test_device_readers(red):
    run = SimpleNamespace(trace=red, device_kind="TPU v5 lite")
    idle = harness.load_reader("device_idle_share")(run)
    assert idle == pytest.approx(100 * (1 - red.busy_s / red.window_s))
    per_call = harness.load_reader("engine_device_ms")(run)
    assert per_call == pytest.approx(
        1e3 * red.busy_in_complete_s / red.n_complete)
    calls = red.ops_matching(rmq.matches)
    assert calls and all(rmq.lanes(c) > 0 for c in calls)
    share = harness.load_reader("rmq_roofline")(run)
    assert 0 < share <= 100
