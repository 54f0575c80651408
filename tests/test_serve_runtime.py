"""Online runtime parity (ISSUE 4): ANY arrival interleaving / batch-formation
schedule must yield results bit-identical to direct per-request
``QACFrontend`` calls — across the exact-LRU hit path, the session
filter-first fast path (prefix extension AND term-completion-by-space), the
trivial reject path, session backtracking (deleted characters), mixed
per-request k, and every scheduler trigger (full bucket, deadline, drain).

Scheduling can never change WHAT a request answers (each lane is computed
independently and caches only ever replay complete match sets), so these
tests drive the scheduler through pathological configs — max_batch=1, zero
slack, tiny/disabled caches — and still demand bit-identity.
"""
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import build_qac_index, parse_queries, INF_DOCID
from repro.serve import QACFrontend
from repro.serve.runtime import (QACOnlineRuntime, RuntimeConfig,
                                 prepare_requests, run_naive_trace)
from repro.text import (SynthLogConfig, generate_query_log,
                        KeystrokeTraceConfig, generate_keystroke_trace)


@pytest.fixture(scope="module")
def built():
    qs, sc = generate_query_log(SynthLogConfig(n_queries=600, vocab_size=150,
                                               mean_term_chars=4.0, seed=5))
    qidx, kept, _ = build_qac_index(qs, sc)
    # one shared frontend: the jit cache stays warm across tests, and using
    # the same instance for runtime and reference is sound (complete() is a
    # pure function of its inputs)
    fe = QACFrontend(qidx, k=10, specialize_list_pad=False)
    return qidx, kept, fe


def _direct_rows(fe, reqs):
    """The reference: every request dispatched alone, straight through the
    frontend, at its own k."""
    return [np.asarray(fe.complete(
        r.pids[None], np.asarray([r.plen], np.int32), r.suf[None],
        np.asarray([r.slen], np.int32), k=r.k))[0] for r in reqs]


def _assert_parity(fe, reqs, got):
    want = _direct_rows(fe, reqs)
    assert len(got) == len(reqs)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(
            g, w, err_msg=f"request {i}: {reqs[i].query!r}")


def _keystrokes(queries, session0=0, t0=0.0, gap=1000.0):
    """Explicit keystroke events: every prefix of every query, one session
    per query, fixed inter-arrival gap (deterministic schedules)."""
    events, t = [], t0
    for s, q in enumerate(queries):
        for n in range(1, len(q) + 1):
            t += gap
            events.append((t, session0 + s, q[:n]))
    return sorted(events)


# NOTE on determinism: parity is schedule-independent (every path computes
# or replays the exact per-request answer), but HIT COUNTS are not — a slow
# engine dispatch (e.g. a jit compile on a loaded runner) can push results
# past a duplicate's arrival, turning a would-be hit into a miss. Tests
# that assert hit counts therefore use the synchronous config
# (max_batch=1, slack=0): every miss is served inside submit(), before the
# next arrival is processed, so cache contents — and hence hit counts —
# are a pure function of the trace.
_SYNC = dict(max_batch=1, slack_us=0.0)


# --------------------------------------------------------------- fast paths
def test_synthetic_trace_parity_and_hits(built):
    qidx, kept, fe = built
    trace = generate_keystroke_trace(kept, KeystrokeTraceConfig(
        n_sessions=12, mean_keystroke_ms=5.0, session_spread_ms=20.0,
        seed=3))
    reqs = prepare_requests(qidx, trace, k=10)
    rt = QACOnlineRuntime(fe, RuntimeConfig(max_batch=8, slack_us=2_000.0))
    got = rt.run_trace(reqs)
    _assert_parity(fe, reqs, got)
    s = rt.telemetry.snapshot()
    assert s["paths"]["miss"] > 0              # the first arrival always is
    assert s["n_requests"] == len(reqs) == sum(s["paths"].values())
    assert max(s["batch_hist"]) <= 8
    # hit counts: deterministic under the synchronous schedule
    rt2 = QACOnlineRuntime(fe, RuntimeConfig(**_SYNC))
    got2 = rt2.run_trace(reqs)
    for g, g2 in zip(got, got2):
        np.testing.assert_array_equal(g, g2)
    s2 = rt2.telemetry.snapshot()
    assert s2["paths"]["hit_exact"] > 0 and s2["paths"]["hit_session"] > 0


def test_session_filter_path_is_exact(built):
    """A session typing one long multi-term query end to end: once a prefix
    has < k matches the whole tail must be served by host-side filtering of
    the session's complete set — including across the space that promotes
    the suffix into a prefix term — bit-identical to the engine."""
    qidx, kept, fe = built
    target = max((q for q in kept if len(q.split()) >= 2), key=len)
    reqs = prepare_requests(qidx, _keystrokes([target + " "]), k=64)
    rt = QACOnlineRuntime(fe, RuntimeConfig(**_SYNC))
    got = rt.run_trace(reqs)
    _assert_parity(fe, reqs, got)
    # k=64 on a ~600-completion corpus: deep prefixes are complete (<k
    # matches), so the filter path must have fired
    assert rt.telemetry.paths["hit_session"] >= 1


def test_backtracking_hits_the_exact_cache(built):
    """Deleting characters GROWS the match set — the session filter must
    refuse it, and the re-typed shorter prefixes must come back verbatim
    from the exact LRU populated on the way in."""
    qidx, kept, fe = built
    q = max((s for s in kept if len(s.split()) == 1), key=len)
    strokes = [q[:n] for n in range(1, len(q) + 1)]          # type it out
    strokes += [q[:n] for n in range(len(q) - 1, 0, -1)]     # delete it all
    events = [(1000.0 * i, 7, s) for i, s in enumerate(strokes)]
    reqs = prepare_requests(qidx, events, k=10)
    rt = QACOnlineRuntime(fe, RuntimeConfig(**_SYNC))
    got = rt.run_trace(reqs)
    _assert_parity(fe, reqs, got)
    # every backtracked prefix was served earlier in the same session
    assert rt.telemetry.paths["hit_exact"] >= len(q) - 1


def test_trivial_reject_path(built):
    """Unknown-term and empty-suffix-range requests short-circuit to all-INF
    without an engine dispatch — exactly what the engines return."""
    qidx, kept, fe = built
    base = kept[0].split()[0]
    events = _keystrokes(["zzzzzzqx", base + " zzzzzzqx", "qzzzzzy zz"],
                         gap=500.0)
    reqs = prepare_requests(qidx, events, k=10)
    rt = QACOnlineRuntime(fe, RuntimeConfig(max_batch=4, slack_us=100.0))
    got = rt.run_trace(reqs)
    _assert_parity(fe, reqs, got)
    assert rt.telemetry.paths["trivial"] > 0
    assert all((g == INF_DOCID).all() for g, r in zip(got, reqs)
               if "zzz" in r.query.split()[-1])


def test_truncated_multi_scan_never_poisons_session_cache(built):
    """``conjunctive_multi`` stops scanning its driver list after
    tile * max_tiles docids, so an INF-padded row is NOT always the
    complete match set — the session store must refuse to derive a filter
    set from a possibly-truncated scan (``_scan_exact``), or a later
    keystroke could answer from a poisoned set and break parity. Force
    truncation with a tiny scan budget and check both the guard and
    end-to-end parity."""
    qidx, kept, _ = built
    fe2 = QACFrontend(qidx, k=10, tile=8, max_tiles=1,
                      specialize_list_pad=False)
    rt = QACOnlineRuntime(fe2, RuntimeConfig(**_SYNC))
    long_term = int(np.argmax(fe2._list_lens))
    assert int(fe2._list_lens[long_term]) > 8
    fake = prepare_requests(qidx, [(0.0, 0, kept[0])], k=10)[0]
    fake.pids = np.asarray([long_term] + [0] * (fake.pids.size - 1), np.int32)
    fake.plen = 1
    assert not rt._scan_exact(fake)          # long driver => unprovable
    assert rt._scan_exact(prepare_requests(
        qidx, [(0.0, 0, kept[0].split()[0])], k=10)[0])  # single-term: exact
    # the sharpest shape: a single-term prefix (exact engine -> complete
    # session set) followed by the space that promotes a LONG-listed term
    # into the prefix — the new request's own driver scan truncates, so
    # _reusable must refuse the filter path and reproduce the engine's
    # truncated answer verbatim
    lens = np.asarray(fe2._list_lens)
    long_toks = [q.split()[0] for q in kept if len(q.split()) >= 2
                 and lens[np.clip(qidx.dictionary.id_of(q.split()[0]), 0,
                                  len(lens) - 1)] > 8]
    assert long_toks, "corpus lost its long posting lists?"
    promoted = [t + " " for t in long_toks[:3]]
    # k=64 so the single-term stage is COMPLETE (< k matches -> a session
    # set forms) while 'tok ' matches every docid of the long list — more
    # than the 8 the engine scans. Without the _reusable exactness guard
    # the filter path answers correctly where the engine truncates, which
    # is exactly the parity break this test must catch.
    rt64 = QACOnlineRuntime(fe2, RuntimeConfig(**_SYNC))
    reqs = prepare_requests(qidx, _keystrokes(promoted), k=64)
    got = rt64.run_trace(reqs)
    _assert_parity(fe2, reqs, got)
    # end-to-end: sessions typing multi-term queries under the truncating
    # frontend must still match its own direct per-request answers
    multis = [q for q in kept if len(q.split()) >= 2][:6]
    reqs = prepare_requests(qidx, _keystrokes(multis), k=10)
    got = rt.run_trace(reqs)
    _assert_parity(fe2, reqs, got)


# ---------------------------------------------------------------- scheduler
def test_full_bucket_and_drain_triggers(built):
    """A burst arriving faster than the deadline forces full-bucket
    dispatches; the tail drains. Caches off so every request queues."""
    qidx, kept, fe = built
    queries = [kept[i % len(kept)] for i in range(11)]
    events = [(float(i), i, q) for i, q in enumerate(queries)]  # 1us apart
    reqs = prepare_requests(qidx, events, k=10)
    rt = QACOnlineRuntime(fe, RuntimeConfig(
        max_batch=4, slack_us=1e9, cache_entries=0, session_entries=0))
    got = rt.run_trace(reqs)
    _assert_parity(fe, reqs, got)
    s = rt.telemetry.snapshot()
    assert s["paths"].get("miss", 0) == len(reqs)
    assert s["triggers"].get("full", 0) >= 2
    assert s["triggers"].get("drain", 0) >= 1
    assert s["batch_hist"].get(4, 0) >= 2


def test_tick_fires_deadlines_without_new_arrivals(built):
    """Live mode: a queued request whose deadline passes during a traffic
    lull must be dispatched by tick(now), not wait for the next submit."""
    qidx, kept, fe = built
    reqs = prepare_requests(qidx, [(0.0, 0, kept[10])], k=10)
    rt = QACOnlineRuntime(fe, RuntimeConfig(
        max_batch=64, slack_us=1_000.0, cache_entries=0, session_entries=0))
    rt.submit(reqs[0])
    assert len(rt.queue) == 1
    rt.tick(500.0)                    # before the deadline: still queued
    assert len(rt.queue) == 1
    rt.tick(2_000.0)                  # past it: dispatched
    assert not rt.queue and rt.telemetry.paths["miss"] == 1


def test_one_request_per_dispatch_matches_naive(built):
    """max_batch=1 + caches off degenerates to the naive baseline."""
    qidx, kept, fe = built
    events = _keystrokes([kept[3], kept[40]], gap=2_000.0)
    reqs = prepare_requests(qidx, events, k=10)
    rt = QACOnlineRuntime(fe, RuntimeConfig(
        max_batch=1, slack_us=0.0, cache_entries=0, session_entries=0))
    got = rt.run_trace(reqs)
    naive_rows, stats = run_naive_trace(fe, reqs, warm=False)
    for g, w in zip(got, naive_rows):
        np.testing.assert_array_equal(g, w)
    assert rt.telemetry.snapshot()["mean_batch_size"] == 1.0
    assert stats["n_requests"] == len(reqs)


def test_mixed_per_request_k(built):
    """Heterogeneous k in one trace: batches dispatch through the
    frontend's per-k path, caches key on (prefix, k)."""
    qidx, kept, fe = built
    events = _keystrokes([kept[5], kept[17], kept[31]], gap=300.0)
    ks = np.asarray([(3, 10, 33)[i % 3] for i in range(len(events))])
    reqs = prepare_requests(qidx, events, k=ks)
    rt = QACOnlineRuntime(fe, RuntimeConfig(max_batch=8, slack_us=1_000.0))
    got = rt.run_trace(reqs)
    _assert_parity(fe, reqs, got)
    for r, g in zip(reqs, got):
        assert g.shape == (r.k,)


# ------------------------------------------------- randomized interleavings
def _random_schedule_example(built, draw_int, draw_float, draw_from):
    """One randomized trace + scheduler config, checked for parity. The
    draw_* hooks are either hypothesis draws or a seeded numpy rng, so the
    property gets shrinkable exploration where hypothesis is installed and
    a deterministic seeded sweep everywhere else."""
    qidx, kept, fe = built
    n_sessions = draw_int(1, 5)
    pool = kept[:: max(1, len(kept) // 40)]      # small pool => collisions
    events = []
    for s in range(n_sessions):
        target = draw_from(pool)
        t = draw_float(0.0, 2e4)
        pos = draw_int(1, len(target))
        for _ in range(draw_int(1, 7)):
            events.append((t, s, target[:pos]))
            t += draw_float(1.0, 3e4)
            pos = max(1, min(len(target),
                             pos + draw_from([1, 1, 1, 2, -1, -2])))
    events.sort(key=lambda e: e[0])
    cfg = RuntimeConfig(
        max_batch=draw_from([1, 2, 5, 8]),
        slack_us=draw_from([0.0, 500.0, 1e5]),
        cache_entries=draw_from([0, 3, 1 << 10]),
        session_entries=draw_from([0, 2, 1 << 10]))
    reqs = prepare_requests(qidx, events, k=draw_from([3, 10, 33]))
    got = QACOnlineRuntime(fe, cfg).run_trace(reqs)
    _assert_parity(fe, reqs, got)


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_runtime_parity_any_interleaving(built, data):
    """Random sessions, random prefix walks (forward AND backward), random
    arrival gaps, random scheduler/cache configs — bit-identical to direct
    per-request frontend calls, always."""
    _random_schedule_example(
        built,
        lambda a, b: data.draw(st.integers(a, b)),
        lambda a, b: data.draw(st.floats(a, b)),
        lambda xs: data.draw(st.sampled_from(xs)))


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_runtime_parity_seeded_schedules(built, seed):
    """The same property as the hypothesis test, driven by a seeded rng so
    it runs (deterministically) even where hypothesis is not installed."""
    rng = np.random.default_rng(1234 + seed)
    _random_schedule_example(
        built,
        lambda a, b: int(rng.integers(a, b + 1)),
        lambda a, b: float(rng.uniform(a, b)),
        lambda xs: xs[int(rng.integers(0, len(xs)))])


# -------------------------------------------------- telemetry (ISSUE 8)
def test_telemetry_percentiles_pinned_to_numpy():
    """snapshot()'s quantile math is np.percentile, verbatim — no
    hand-rolled interpolation allowed to drift."""
    from repro.serve.runtime import RuntimeTelemetry
    t = RuntimeTelemetry()
    lats = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0, 535.0, 89.0, 79.0]
    for x in lats:
        t.record("miss", x)
    s = t.snapshot()
    for p in (50, 95, 99):
        assert s[f"p{p}_us"] == float(np.percentile(lats, p))
    assert s["mean_us"] == pytest.approx(np.mean(lats))
    assert s["max_us"] == max(lats)
    assert s["deadline_violations"] == 0
    assert s["max_queue_depth"] == 0


def test_telemetry_deadline_violations_and_queue_gauge(built):
    """Two same-instant arrivals under max_batch=1/slack=0: the second
    dispatch starts at server_free > its deadline — exactly one violation
    per such pile-up. Then a held queue pins the max-depth gauge."""
    qidx, kept, fe = built
    words = sorted({q.split()[0] for q in kept})[:6]
    reqs = prepare_requests(qidx, [(0.0, s, w) for s, w in enumerate(words)],
                            k=10)
    rt = QACOnlineRuntime(fe, RuntimeConfig(**_SYNC))
    rt.run_trace(reqs)
    s = rt.telemetry.snapshot()
    # first dispatch starts exactly at its deadline (t=0): not a violation;
    # every later one starts behind the busy server: violation
    assert s["deadline_violations"] == len(reqs) - 1
    # huge slack + batch: all requests sit queued until drain
    rt2 = QACOnlineRuntime(fe, RuntimeConfig(max_batch=64, slack_us=1e9))
    rt2.run_trace(reqs)
    s2 = rt2.telemetry.snapshot()
    assert s2["max_queue_depth"] == len(reqs)
    assert s2["max_queue_depth"] == s2["queue_peak"]   # back-compat alias
    assert s2["deadline_violations"] == 0              # drain fires in time


# ---------------------------------------------- open-loop traces (ISSUE 8)
def test_trace_target_qps_rescales_and_is_deterministic(built):
    qidx, kept, fe = built
    base_cfg = KeystrokeTraceConfig(n_sessions=8, mean_keystroke_ms=50.0,
                                    seed=13)
    base = generate_keystroke_trace(kept, base_cfg)
    for qps in (50.0, 400.0):
        cfg = KeystrokeTraceConfig(n_sessions=8, mean_keystroke_ms=50.0,
                                   seed=13, target_qps=qps)
        tr = generate_keystroke_trace(kept, cfg)
        tr2 = generate_keystroke_trace(kept, cfg)
        assert tr == tr2                        # seeded-deterministic
        # same REQUEST SET, rescaled time axis
        assert [(s, q) for _, s, q in tr] == [(s, q) for _, s, q in base]
        span_s = (tr[-1][0] - tr[0][0]) / 1e6
        assert (len(tr) - 1) / span_s == pytest.approx(qps, rel=1e-6)
        assert tr[0][0] == 0.0
        # ordering preserved -> still a valid runtime trace
        assert all(a[0] <= b[0] for a, b in zip(tr, tr[1:]))
    with pytest.raises(ValueError):
        generate_keystroke_trace(kept, KeystrokeTraceConfig(
            n_sessions=2, seed=13, target_qps=-1.0))


# ------------------------------------------- real-clock stages (obs/tracing)
@pytest.fixture(scope="module")
def staged(built):
    """One trace served with the stage records on (they always are)."""
    qidx, kept, fe = built
    trace = generate_keystroke_trace(kept, KeystrokeTraceConfig(
        n_sessions=24, mean_keystroke_ms=5.0, session_spread_ms=20.0,
        seed=21))
    reqs = prepare_requests(qidx, trace, k=10)
    rt = QACOnlineRuntime(fe, RuntimeConfig(max_batch=16, slack_us=2_000.0))
    got = rt.run_trace(reqs)
    return reqs, rt, got


def _by_dispatch(log):
    out = {}
    for name, t0, t1, d, attrs in log.spans:
        out.setdefault(d, []).append((name, t0, t1, attrs))
    return out


def test_dispatch_stages_tile_each_dispatch(staged):
    _, rt, _ = staged
    disp = _by_dispatch(rt.telemetry.stages)
    assert len(disp) == len(rt.telemetry.batch_sizes) > 0
    for d, spans in disp.items():
        (p0, p1), = [(t0, t1) for n, t0, t1, _ in spans
                     if n == "runtime.dispatch"]
        kids = [(n, t0, t1) for n, t0, t1, _ in spans
                if n != "runtime.dispatch"]
        names = [n for n, _, _ in kids]
        assert names[0] == "runtime.batch" and names[-1] == "runtime.scatter"
        assert "frontend.route" in names
        assert any(n.endswith(".launch") for n in names)
        assert any(n.endswith(".sync") for n in names)
        assert all(p0 <= t0 <= t1 <= p1 for _, t0, t1 in kids)
        covered = sum(t1 - t0 for _, t0, t1 in kids)
        assert abs((p1 - p0) - covered) <= max(0.02 * (p1 - p0), 20_000), d


def test_queue_wait_once_per_miss_naming_its_dispatch(staged):
    reqs, rt, _ = staged
    log = rt.telemetry.stages
    starts = {d: t0 for n, t0, _, d, _ in log.spans
              if n == "runtime.dispatch"}
    misses = sorted(r.idx for r in reqs if rt.done_path[r.idx] == "miss")
    assert misses and sorted(w[0] for w in log.waits) == misses
    for idx, t_enq, t_start, d in log.waits:
        assert t_start - t_enq >= 0
        assert starts[d] == t_start
    assert {w[3] for w in log.waits} == set(starts)


def test_lookup_recorded_once_per_request(staged):
    reqs, rt, _ = staged
    lookups = rt.telemetry.stages.lookups
    assert sorted(i for i, _ in lookups) == [r.idx for r in reqs]
    assert all(ns >= 0 for _, ns in lookups)


def test_stage_records_leave_answers_bit_identical(built, staged):
    """The stage-free path is a frontend call outside any dispatch: it
    records nothing, and every served answer equals it."""
    _, _, fe = built
    reqs, rt, got = staged
    n = len(rt.telemetry.stages.spans)
    _assert_parity(fe, reqs, got)
    assert len(rt.telemetry.stages.spans) == n


def test_engine_wall_us_sums_the_dispatch_records(staged):
    _, rt, _ = staged
    total = sum(t1 - t0 for n, t0, t1, _, _ in rt.telemetry.stages.spans
                if n == "runtime.dispatch")
    snap = rt.telemetry.snapshot()
    assert snap["engine_wall_us"] == pytest.approx(total / 1e3) and total > 0


def test_forced_fallback_shows_a_single_full_stage(built):
    """trips=1 leaves lanes unfinished: the exact fallback runs, and its
    launch record is the fallback count."""
    qidx, kept, fe = built
    fe1 = QACFrontend(qidx, k=10, trips=1, specialize_list_pad=False)
    words = sorted({q.split()[0] for q in kept})[:12]
    reqs = prepare_requests(qidx, [(float(i), i, w[:2])
                                   for i, w in enumerate(words)], k=10)
    rt = QACOnlineRuntime(fe1, RuntimeConfig(
        max_batch=8, slack_us=1e9, cache_entries=0, session_entries=0))
    got = rt.run_trace(reqs)
    _assert_parity(fe, reqs, got)
    names = [n for n, *_ in rt.telemetry.stages.spans]
    n_full = names.count("engine.single_full.launch")
    assert n_full >= 1 and n_full == fe1.stats["single_fallbacks"]
    assert names.count("engine.single_full.sync") == n_full


def test_reset_clears_the_stage_records(built):
    qidx, kept, fe = built
    reqs = prepare_requests(qidx, [(0.0, 0, kept[7])], k=10)
    rt = QACOnlineRuntime(fe, RuntimeConfig(max_batch=1, slack_us=0.0))
    rt.run_trace(reqs)
    log = rt.telemetry.stages
    assert log.spans and log.lookups and log.waits
    rt.reset()
    log = rt.telemetry.stages
    assert not (log.spans or log.lookups or log.waits)
    assert rt.telemetry.snapshot()["engine_wall_us"] == 0.0
