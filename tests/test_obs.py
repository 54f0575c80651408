"""Observability stack (ISSUE 10): metrics/tracing/jit-audit/SLO units,
the one-percentile-implementation contract, span-tree invariants under
arbitrary schedules (hypothesis), and the bench regression gate's diff
logic. The end-to-end acceptance (overhead cap, negative jit-audit
control) lives in benchmarks/bench_qac_obs.py; here we pin the contracts
every layer relies on."""
import json
import os
import sys

import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import build_qac_index
from repro.obs import (JitAuditError, JitAuditor, MetricsRegistry, ObsConfig,
                       SLOMonitor, Tracer)
from repro.obs.metrics import Histogram, fmt, percentiles
from repro.obs.tracing import load_jsonl, request_trees, span_children
from repro.serve import QACFrontend
from repro.serve.runtime import (QACOnlineRuntime, RuntimeConfig,
                                 prepare_requests)
from repro.text import (KeystrokeTraceConfig, SynthLogConfig,
                        generate_keystroke_trace, generate_query_log)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.common import compare_results, metric_direction  # noqa: E402


@pytest.fixture(scope="module")
def built():
    qs, sc = generate_query_log(SynthLogConfig(n_queries=500, vocab_size=120,
                                               mean_term_chars=4.0, seed=9))
    qidx, kept, _ = build_qac_index(qs, sc)
    fe = QACFrontend(qidx, k=10, specialize_list_pad=False)
    return qidx, kept, fe


# ------------------------------------------------------------- percentiles
def test_percentiles_pinned_to_numpy():
    """THE percentile implementation (every serving snapshot routes here)
    is np.percentile, verbatim."""
    vals = [3.0, 1.0, 4.0, 1.0, 5.0, 926.0, 5.0, 3.0, 589.0]
    p = percentiles(vals, (50, 95, 99), mean=True, vmax=True)
    for q in (50, 95, 99):
        assert p[f"p{q}_us"] == float(np.percentile(vals, q))
    assert p["mean_us"] == pytest.approx(np.mean(vals))
    assert p["max_us"] == max(vals)


def test_percentiles_empty_is_none_not_nan():
    """Empty latency lists -> explicit None per key (the snapshot contract
    ISSUE 10 fixes): no NaN, no fake 0.0, no crash."""
    p = percentiles([], (50, 99), mean=True, vmax=True)
    assert p == {"p50_us": None, "p99_us": None,
                 "mean_us": None, "max_us": None}
    assert percentiles([], suffix="_ms") == {
        "p50_ms": None, "p95_ms": None, "p99_ms": None}


def test_fmt_renders_none_as_na():
    assert fmt(None) == "n/a"
    assert fmt(1234.0, 1e3, 2, "ms") == "1.23ms"
    assert fmt(50.0) == "50"


def test_empty_runtime_telemetry_snapshot():
    """RuntimeTelemetry on zero requests: None percentiles, no crash."""
    from repro.serve.runtime import RuntimeTelemetry
    s = RuntimeTelemetry().snapshot()
    assert s["n_requests"] == 0
    assert s["p50_us"] is None and s["p99_us"] is None
    assert s["mean_us"] is None and s["max_us"] is None
    assert s["mean_batch_size"] is None
    json.dumps(s)                        # schema stays JSON-serializable


def test_empty_cluster_telemetry_snapshot():
    from repro.serve.cluster import ClusterTelemetry
    s = ClusterTelemetry().snapshot()
    assert s["interactive_p99_us"] is None
    assert s["shed_rate"] == 0.0
    json.dumps(s)


# ---------------------------------------------------------------- registry
def test_histogram_reservoir():
    h = Histogram(capacity=4)
    for x in (5.0, 1.0, 3.0):
        h.observe(x)
    s = h.snapshot()
    assert s["n"] == 3 and "truncated" not in s
    assert s["p50"] == float(np.percentile([5.0, 1.0, 3.0], 50))
    assert s["max"] == 5.0
    for x in range(10):
        h.observe(float(x))
    s = h.snapshot()
    assert s["n"] == 13 and s["truncated"]   # count/max stay exact
    assert s["max"] == 9.0


def test_metrics_registry_schema():
    reg = MetricsRegistry()
    reg.counter("requests", 3)
    reg.counter("requests")
    reg.gauge("queue_depth", 7.0)
    reg.observe("lat", 10.0)
    reg.observe("lat", 20.0)
    reg.register_collector("rt", lambda: {"x": 1})
    with pytest.raises(TypeError):
        reg.register_collector("bad", 42)
    s = reg.snapshot()
    assert s["counters"] == {"requests": 4}
    assert s["gauges"] == {"queue_depth": 7.0}
    assert s["histograms"]["lat"]["n"] == 2
    assert s["collectors"]["rt"] == {"x": 1}
    # re-register replaces (the freshness layer re-registers per reset)
    reg.register_collector("rt", lambda: {"x": 2})
    assert reg.snapshot()["collectors"]["rt"] == {"x": 2}


# ------------------------------------------------------------------ tracer
def test_tracer_sampling_and_roundtrip(tmp_path):
    tr = Tracer(sample_every=4)
    assert [i for i in range(8) if tr.want(i)] == [0, 4]
    root = tr.span("request", 0.0, 100.0, req=0, path="miss")
    tr.span("queue.wait", 0.0, 60.0, cat="queue", req=0, parent=root)
    tr.span("engine.service", 60.0, 40.0, cat="engine", req=0, parent=root)
    tr.instant("jit.compile", 5.0, cat="jit", key="k")
    p = tr.to_jsonl(str(tmp_path / "t.jsonl"))
    spans, instants = load_jsonl(p)
    assert len(spans) == 3 and len(instants) == 1
    trees = request_trees(spans)
    r, kids = trees[0]
    assert r["attrs"]["path"] == "miss" and len(kids) == 2
    assert sum(c["dur_us"] for c in kids) == r["dur_us"]
    # chrome export is well-formed trace-event JSON
    cp = tr.to_chrome(str(tmp_path / "t.json"))
    with open(cp) as f:
        ev = json.load(f)["traceEvents"]
    assert {e["ph"] for e in ev} == {"X", "i"}


def test_tracer_capacity_and_clear():
    tr = Tracer(capacity=2)
    ids = [tr.span("s", 0.0, 1.0) for _ in range(4)]
    assert ids[2] is None and tr.dropped == 2
    seen = set(ids[:2])
    tr.clear()
    assert tr.spans == [] and tr.dropped == 0
    nid = tr.span("s", 0.0, 1.0)
    assert nid not in seen            # ids advance across clears
    with pytest.raises(ValueError):
        Tracer(sample_every=0)


# --------------------------------------------------------------- jit audit
def test_jit_auditor_freeze_and_violations():
    aud = JitAuditor()
    f = aud.wrap(("single", 8, 10, 0), lambda x: x + 1)
    assert f(1) == 2 and f(2) == 3
    assert len(aud.compiles) == 1     # only the first call records
    aud.freeze()
    aud.assert_closed()               # nothing post-freeze yet
    g = aud.wrap(("multi", 8, 10, 16), lambda x: x * 2, label="intersect")
    assert g(3) == 6
    assert len(aud.violations) == 1
    assert aud.violations[0]["label"] == "intersect"
    with pytest.raises(JitAuditError):
        aud.assert_closed()
    snap = aud.snapshot()
    assert snap["n_variants"] == 2 and snap["n_violations"] == 1
    json.dumps(snap)


def test_jit_auditor_strict_raises_on_the_spot():
    aud = JitAuditor(strict=True)
    aud.freeze()
    f = aud.wrap("k", lambda: 0)
    with pytest.raises(JitAuditError):
        f()


def test_jit_auditor_compile_instants_land_in_trace():
    tr = Tracer()
    aud = JitAuditor(tracer=tr)
    aud.wrap("k", lambda: 0)()
    assert [e["name"] for e in tr.instants] == ["jit.compile"]


# --------------------------------------------------------------------- SLO
def test_slo_burn_rate_math():
    """Burn = violation fraction / error budget, exactly."""
    slo = SLOMonitor(target_us=100.0, objective=0.9,
                     windows=((1_000.0, 100.0, 2.0),))
    for i in range(10):               # 10 samples, 3 violations
        slo.observe(float(i * 10), 500.0 if i in (2, 5, 9) else 50.0)
    assert slo.burn_rate(1_000.0) == pytest.approx((3 / 10) / 0.1)
    ev = slo.evaluate()
    assert ev["n_requests"] == 10 and ev["n_violations"] == 3
    assert ev["compliance"] == pytest.approx(0.7)
    a = ev["alerts"][0]
    assert a["long_burn"] == pytest.approx(3.0)
    # short window (trailing 100us ending at t=90): samples t in [-10, 90]
    # -> all 10; the pair fires only when BOTH exceed the threshold
    assert a["firing"] == (a["long_burn"] >= 2.0 and a["short_burn"] >= 2.0)
    assert a["firing"]


def test_slo_multi_window_needs_both():
    """A burst inside the short window alone must NOT fire (the long
    window proves the burn is sustained)."""
    slo = SLOMonitor(target_us=100.0, objective=0.9,
                     windows=((10_000.0, 100.0, 3.0),))
    for i in range(100):
        slo.observe(float(i * 100), 50.0)   # 10ms of clean traffic
    for i in range(3):                      # then a 3-violation burst
        slo.observe(10_000.0 + i, 500.0)
    ev = slo.evaluate()
    a = ev["alerts"][0]
    assert a["short_burn"] >= 3.0           # short window: all bad
    assert a["long_burn"] < 3.0             # long window: diluted
    assert not a["firing"]


def test_slo_empty_and_validation():
    slo = SLOMonitor()
    assert slo.burn_rate(1e6) is None
    assert slo.evaluate()["compliance"] is None
    with pytest.raises(ValueError):
        SLOMonitor(objective=1.0)
    with pytest.raises(ValueError):
        SLOMonitor(windows=((100.0, 200.0, 1.0),))   # short > long
    with pytest.raises(ValueError):
        ObsConfig(trace_sample_every=0)


# --------------------------------------------------------- regression gate
def test_metric_direction_heuristics():
    assert metric_direction("qac_online_p99_us") == "lower"
    assert metric_direction("qac_postings_bpi") == "lower"
    assert metric_direction("qac_obs_overhead_ratio") == "lower"
    assert metric_direction("qac_cluster_shed_rate_burst") == "lower"
    assert metric_direction("qac_cluster_interactive_qps") == "higher"
    assert metric_direction("qac_online_cache_hit_rate") == "higher"
    assert metric_direction("qac_freshness_hit_rate_recovery") == "higher"
    assert metric_direction("some_novel_score") == "unknown"


def test_compare_results_gates_both_directions():
    base = {"a_p99_us": 100.0, "b_hit_rate": 0.8, "c_novel": 1.0,
            "gone_us": 5.0}
    # within tolerance: no regressions
    rep = compare_results({"a_p99_us": 140.0, "b_hit_rate": 0.75,
                           "c_novel": 99.0}, base, tolerance=0.5)
    assert rep["regressions"] == []
    assert rep["missing"] == ["gone_us"]
    # lower-better metric moving up past tolerance regresses
    rep = compare_results({"a_p99_us": 151.0}, base, tolerance=0.5)
    assert rep["regressions"] == ["a_p99_us"]
    # higher-better metric moving down past tolerance regresses
    rep = compare_results({"b_hit_rate": 0.3}, base, tolerance=0.5)
    assert rep["regressions"] == ["b_hit_rate"]
    # unknown-direction metrics are reported but never gate
    rep = compare_results({"c_novel": 1e9}, base, tolerance=0.5)
    assert rep["regressions"] == []
    assert [r["status"] for r in rep["rows"]] == ["ok"]
    with pytest.raises(ValueError):
        compare_results({}, {}, tolerance=-0.1)


# --------------------------------------- span-tree invariants (hypothesis)
def _traced_run(built, n_sessions, seed, sample_every, max_batch, slack_us):
    qidx, kept, fe = built
    trace = generate_keystroke_trace(kept, KeystrokeTraceConfig(
        n_sessions=n_sessions, mean_keystroke_ms=5.0, session_spread_ms=20.0,
        seed=seed))
    reqs = prepare_requests(qidx, trace, k=10)
    cfg = RuntimeConfig(max_batch=max_batch, slack_us=slack_us)
    tr = Tracer(sample_every=sample_every)
    rt = QACOnlineRuntime(fe, cfg, tracer=tr)
    got = rt.run_trace(reqs)
    rt_off = QACOnlineRuntime(fe, cfg)
    want = rt_off.run_trace(reqs)
    return reqs, rt, tr, got, want


def _assert_span_invariants(reqs, rt, tr, got, want):
    # 1. tracing never changes answers: bit parity with the untraced run
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(
            g, w, err_msg=f"tracing changed request {i}")
    trees = request_trees(tr.spans)
    sampled = [r for r in reqs if tr.want(r.idx)]
    assert set(trees) == {r.idx for r in sampled}
    kids_by_parent = span_children(tr.spans)
    for r in sampled:
        root, kids = trees[r.idx]
        # 2. the root covers [arrival, completion] on the virtual clock
        assert root["t0_us"] == r.t_us
        lat = rt.done_t_us[r.idx] - r.t_us
        assert root["dur_us"] == pytest.approx(lat, abs=1e-6)
        # 3. children nest inside the root and partition its interval:
        #    child-sum == e2e latency EXACTLY (same clock arithmetic)
        assert kids, f"request {r.idx} root span has no children"
        t0, t1 = root["t0_us"], root["t0_us"] + root["dur_us"]
        for c in kids:
            assert c["t0_us"] >= t0 - 1e-9
            assert c["t0_us"] + c["dur_us"] <= t1 + 1e-9
            assert kids_by_parent.get(c["id"], []) == []   # depth <= 2
        assert sum(c["dur_us"] for c in kids) == \
            pytest.approx(root["dur_us"], abs=1e-6)
        names = sorted(c["name"] for c in kids)
        if root["attrs"]["path"] == "miss":
            assert names == ["engine.service", "queue.wait"]
        else:
            assert names == [f"cache.{root['attrs']['path']}"]


@pytest.mark.parametrize("seed,sample_every,max_batch,slack_us", [
    (0, 1, 8, 2_000.0), (1, 3, 1, 0.0), (2, 16, 64, 500.0),
])
def test_span_tree_invariants_seeded(built, seed, sample_every, max_batch,
                                     slack_us):
    _assert_span_invariants(
        *_traced_run(built, 10, seed, sample_every, max_batch, slack_us))


# hypothesis is fine with module-scoped fixtures (its health check only
# rejects function scope, which would be silently reused across examples)
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), sample_every=st.integers(1, 17),
       max_batch=st.sampled_from([1, 4, 8, 32]),
       slack_us=st.floats(0.0, 5_000.0))
def test_span_tree_invariants_hypothesis(built, seed, sample_every,
                                         max_batch, slack_us):
    _assert_span_invariants(*_traced_run(
        built, 6, seed, sample_every, max_batch, slack_us))


def test_obs_config_factories():
    cfg = ObsConfig(trace_sample_every=4, slo_target_us=10_000.0)
    tr = cfg.tracer()
    assert tr.sample_every == 4
    aud = cfg.auditor(tracer=tr)
    assert aud.tracer is tr
    assert cfg.slo_monitor().target_us == 10_000.0
    assert isinstance(cfg.registry(), MetricsRegistry)


# ------------------------------------------------------ real-clock stages
def _served(built, **cfg):
    qidx, kept, fe = built
    trace = generate_keystroke_trace(kept, KeystrokeTraceConfig(
        n_sessions=8, mean_keystroke_ms=5.0, session_spread_ms=20.0,
        seed=4))
    reqs = prepare_requests(qidx, trace, k=10)
    rt = QACOnlineRuntime(fe, RuntimeConfig(max_batch=8, slack_us=1_000.0),
                          **cfg)
    rt.run_trace(reqs)
    return reqs, rt


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts entries."""

    enabled = False
    entered: list = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("active", [False, True],
                         ids=["profiler_inactive", "profiler_active"])
def test_stage_annotations_only_while_a_profiler_runs(built, monkeypatch,
                                                      active):
    from repro.obs import tracing
    monkeypatch.setattr(_CountingAnnotation, "enabled", active)
    monkeypatch.setattr(_CountingAnnotation, "entered", [])
    monkeypatch.setattr(tracing, "TraceAnnotation", _CountingAnnotation)
    reqs, rt = _served(built)
    entered = _CountingAnnotation.entered
    if not active:
        assert entered == []
    else:
        # one per request's lookup, one per recorded stage
        assert entered.count("runtime.lookup") == len(reqs)
        assert len(entered) == len(reqs) + len(rt.telemetry.stages.spans)


def test_stage_names_stay_clear_of_the_benchmark_spans(built):
    """The benchmark collects its own host spans by name; a program stage
    of the same name would change what those spans measure."""
    from bench import stages, traces
    _, rt = _served(built)
    names = {n for n, *_ in rt.telemetry.stages.spans} | {"runtime.lookup"}
    assert {"runtime.dispatch", "runtime.batch", "frontend.route",
            "runtime.scatter"} <= names
    for n in names:
        assert n not in traces.HOST_SPANS and n != "window"
        assert n.startswith(stages.PROGRAM)


def test_batch_dispatch_span_takes_keys_and_routes_from_the_stages(built):
    tr = Tracer(sample_every=1)
    _, rt = _served(built, tracer=tr)
    batches = [s for s in tr.spans if s["name"] == "batch.dispatch"]
    launches = [(n, a) for n, _, _, _, a in rt.telemetry.stages.spans
                if n.endswith(".launch")]
    assert len(batches) == len(rt.telemetry.batch_sizes)
    keys = [k for b in batches for k in b["attrs"]["jit_keys"]]
    assert keys == [[n.split(".")[1], a["bucket"], a["k"], a["list_pad"]]
                    for n, a in launches]
    for b in batches:
        assert b["attrs"]["routes"]
        assert {k[0] for k in b["attrs"]["jit_keys"]} <= {
            "single", "single_full", "multi"}


def test_gc_clock_records_each_collection_once():
    import gc
    import time
    from repro.obs import tracing
    tracing.install_gc_clock()
    tracing.install_gc_clock()
    assert gc.callbacks.count(tracing._on_gc) == 1
    t = time.perf_counter_ns()
    gc.collect()
    new = [p for p in tracing.gc_pauses() if p[1] >= t]
    assert [g for g, _, _ in new].count(2) == 1
    assert all(t1 >= t0 for _, t0, t1 in new)
