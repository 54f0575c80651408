"""The served path against the plain reference on a tiny corpus, through
the harness's test-only entry (no chip look), and the faults and control
that the check must catch."""
import time

import numpy as np
import pytest

from bench import control, harness
from bench.tests.tiny import TINY_TRAFFIC

INF = 2**31 - 1
CELL = "tiny.sessions"


def execute(root, trace=False, **kw):
    return harness.execute(CELL, 1234567890123, 2.0, trace,
                           t_start=time.perf_counter(), root=root,
                           require_tpu=False, **kw)


@pytest.fixture(scope="module")
def sound(tiny_root):
    return execute(tiny_root)


def test_sound_run_is_correct_on_every_class_and_path(sound):
    result, _, _, chk = sound
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert result["checks"]["mismatched_answers"] == {"value": 0,
                                                      "limit": 0}
    for cls in ("single", "multi"):
        for path in ("miss", "hit_exact", "hit_session"):
            assert chk["strata"].get(f"{cls}.{path}", 0) > 0, chk["strata"]
    assert set(result["metrics"]) == {"setup_s", "goodput_rps"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_control_fails_the_check(tiny_root):
    result, sys_, _, chk = execute(tiny_root, wrap_frontend=control.pruned)
    assert result["correct"] is False
    assert result["checks"]["mismatched_answers"]["value"] > 0
    # what the control served is the reference over the pruned corpus, on
    # every sampled request, whichever path answered it
    keep = control.keep_rows(sys_.ref)
    for i in chk["sample"]:
        r = sys_.reqs[i]
        np.testing.assert_array_equal(
            np.asarray(sys_.rt._results[i])[: r.k],
            sys_.ref.answer(r.query, r.k, n_rows=keep))


def test_another_window_on_one_set_up_is_correct(sound):
    _, sys_, _, _ = sound
    traffic = dict(TINY_TRAFFIC, request_rate=100.0)
    win = harness.rerun(sys_, traffic, 1.0, 987654321987)
    chk = harness.check(sys_, win, 987654321987)
    assert harness.is_correct(chk), chk
    assert chk["strata"]


def _altered_answer(fe, ref):
    complete = fe.complete

    def broken(*args, **kwargs):
        out = np.array(complete(*args, **kwargs))
        first = out[:, 0]
        out[:, 0] = np.where(first < INF, first + 1, first)
        return out

    fe.complete = broken


def _half_batch_left_out(fe, ref):
    complete = fe.complete

    def broken(*args, **kwargs):
        out = np.array(complete(*args, **kwargs))
        out[out.shape[0] // 2:] = INF
        return out

    fe.complete = broken


@pytest.mark.parametrize("fault", [_altered_answer, _half_batch_left_out],
                         ids=["altered_answer", "half_batch_left_out"])
def test_check_catches_a_broken_timed_path(tiny_root, fault):
    result, _, _, chk = execute(tiny_root, wrap_frontend=fault)
    assert result["correct"] is False
    assert chk["mismatched"] > 0


def test_traced_run_reports_the_host_side_layers(tiny_root, tmp_path):
    result, _, _, _ = execute(tiny_root, trace=True,
                              trace_dir=str(tmp_path / "trace"))
    assert result["correct"] is True
    got = result["metrics"]
    for name in ("gen_late_ms", "hit_share", "batch_fill", "service_ms",
                 "jit_compiles"):
        assert name in got, got
    # the CPU has no device plane: the device readers find nothing to read
    for name in ("device_idle_share", "engine_device_ms", "rmq_roofline"):
        assert name not in got
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
