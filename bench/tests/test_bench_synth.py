"""The benchmark's copied generators are deterministic per seed, and its
plain reference agrees with a string-level brute force."""
import numpy as np

from bench.reference import ExactTopK
from bench.synth import (LogParams, TrafficParams, draw_sessions,
                         generate_query_log, schedule)

LOG = LogParams(n_queries=3000, vocab_size=500, mean_terms=2.6,
                mean_term_chars=5.0)
BIG_SEED = 2**31 + 12345


def _pool(seed):
    qlog = generate_query_log(LOG, (seed, 0))
    ref = ExactTopK(qlog.vocab, qlog.term_idx, qlog.n_terms, qlog.scores,
                    max_terms=8, max_term_chars=24)
    return qlog, ref


def test_query_log_is_deterministic_per_seed():
    a, b = generate_query_log(LOG, (BIG_SEED, 0)), generate_query_log(
        LOG, (BIG_SEED, 0))
    c = generate_query_log(LOG, (BIG_SEED + 1, 0))
    assert a.queries == b.queries
    assert np.array_equal(a.scores, b.scores)
    assert np.array_equal(a.term_idx, b.term_idx)
    assert a.queries != c.queries
    # the ids the reference reads spell the strings the program reads
    ends = np.cumsum(a.n_terms)
    q = " ".join(a.vocab[i] for i in a.term_idx[ends[4] - a.n_terms[4]:
                                                ends[4]])
    assert q == a.queries[4]


def test_every_seed_offers_the_same_sessions_in_another_order():
    _, ref = _pool(BIG_SEED)
    tp = TrafficParams(request_rate=40.0)
    sessions, starts = draw_sessions(len(ref), ref.completion, tp, 2.0, 5.0,
                                     (7, 1))
    again = draw_sessions(len(ref), ref.completion, tp, 2.0, 5.0, (7, 1))
    assert (sessions, starts) == again
    # about 200 requests due in the window, session i starting at start i
    assert 200 <= sum(2e6 <= t + dt < 7e6 for s, t in zip(sessions, starts)
                      for dt, _ in s) < 240
    a = schedule(sessions, starts, 7.0, (BIG_SEED, 1))
    b = schedule(sessions, starts, 7.0, (BIG_SEED, 1))
    c = schedule(sessions, starts, 7.0, (BIG_SEED + 1, 1))
    assert a == b and a != c
    times = [t for t, _, _ in a]
    assert times == sorted(times) and times[-1] < 7e6
    # the same typing, started at other times
    typed = {q for _, _, q in a} | {q for _, _, q in c}
    assert typed <= {q for s in sessions for _, q in s}


def test_first_term_traffic_is_single_term():
    _, ref = _pool(3)
    tp = TrafficParams(request_rate=50.0, first_term=True)
    sessions, starts = draw_sessions(len(ref), ref.completion, tp, 1.0, 3.0,
                                     (3, 1))
    ev = schedule(sessions, starts, 4.0, (4, 1))
    assert ev and all(" " not in q for _, _, q in ev)


def _brute(qlog, query, k):
    """Dedup by string, rank by (-score, tokens), filter by the rule."""
    best = {}
    for q, s in zip(qlog.queries, qlog.scores):
        best[q] = max(best.get(q, -1.0), s)
    ranked = sorted(best, key=lambda q: (-best[q], q.split()))
    toks = query.split()
    complete = query.endswith(" ")
    pre = toks if complete else toks[:-1]
    suf = "" if complete else toks[-1]
    out = [d for d, q in enumerate(ranked)
           if set(pre) <= set(q.split())
           and any(t.startswith(suf) for t in q.split())][:k]
    return out + [2**31 - 1] * (k - len(out))


def test_reference_matches_a_string_brute_force():
    qlog, ref = _pool(11)
    rng = np.random.default_rng(0)
    for i in rng.choice(len(ref), 40, replace=False).tolist():
        target = ref.completion(i)
        for cut in (1, len(target) // 2, len(target)):
            query = target[:cut]
            assert ref.answer(query, 10).tolist() == _brute(qlog, query, 10)
    assert ref.answer("zzzzzz qq", 10).tolist() == [2**31 - 1] * 10
