"""QAC core: JAX engines vs the paper's exact host algorithms (oracles)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hyp import given, settings, st

from repro.core import (
    build_qac_index, parse_queries, HostIndex, INF_DOCID,
    prefix_search_topk, conjunctive_multi, single_term_topk,
    TermDictionary, FrontCodedStore, RangeMin, topk_in_range,
)
from repro.core.builder import build_corpus
from repro.core.strings import encode_strings
from repro.text import SynthLogConfig, generate_query_log


def _mini_corpus(seed=0, n=400, vocab=120):
    qs, sc = generate_query_log(SynthLogConfig(n_queries=n, vocab_size=vocab,
                                               mean_term_chars=4.0, seed=seed))
    return qs, sc


@pytest.fixture(scope="module")
def built():
    qs, sc = _mini_corpus()
    qidx, kept, scores = build_qac_index(qs, sc)
    dictionary, rows, sc2, kept2 = build_corpus(qs, sc)
    order = np.lexsort(tuple(rows[:, j] for j in range(rows.shape[1] - 1, -1, -1)) + (-sc2,))
    d_of_row = np.empty(len(rows), dtype=np.int32)
    d_of_row[order] = np.arange(len(rows), dtype=np.int32)
    host = HostIndex(rows, d_of_row, dictionary.n_terms)
    return qidx, kept, host


# ---------------------------------------------------------------- dictionary
def test_dictionary_locate_roundtrip(built):
    qidx, kept, _ = built
    terms = sorted({t for q in kept for t in q.split()})
    sample = terms[:: max(1, len(terms) // 50)]
    chars = encode_strings(sample, qidx.dictionary.max_chars)
    ids = np.asarray(qidx.dictionary.locate(jnp.asarray(chars)))
    for t, i in zip(sample, ids):
        assert i == terms.index(t) + 1
    back = np.asarray(qidx.dictionary.extract(jnp.asarray(ids)))
    for t, row in zip(sample, back):
        assert bytes(row[: len(t)]) == t.encode()


def test_dictionary_locate_absent(built):
    qidx, _, _ = built
    chars = encode_strings(["zzzzzzzzzzzz_nope"], qidx.dictionary.max_chars)
    assert int(qidx.dictionary.locate(jnp.asarray(chars))[0]) == 0


def test_dictionary_locate_prefix_matches_bisect(built):
    qidx, kept, _ = built
    terms = sorted({t for q in kept for t in q.split()})
    rng = np.random.default_rng(0)
    prefixes = ["", "a", "z"] + [
        terms[i][: rng.integers(1, len(terms[i]) + 1)]
        for i in rng.integers(0, len(terms), 25)
    ]
    chars = encode_strings(prefixes, qidx.dictionary.max_chars)
    lens = jnp.asarray([len(p) for p in prefixes], jnp.int32)
    l, r = qidx.dictionary.locate_prefix(jnp.asarray(chars), lens)
    import bisect
    for p, li, ri in zip(prefixes, np.asarray(l), np.asarray(r)):
        lo = bisect.bisect_left(terms, p)
        hi = bisect.bisect_right(terms, p + "\xff")
        assert (li, ri) == (lo + 1, hi + 1), p


# ---------------------------------------------------------------- front coding
@pytest.mark.parametrize("bucket", [4, 16, 64])
def test_front_coding_roundtrip(built, bucket):
    _, kept, _ = built
    fc = FrontCodedStore.build(kept, bucket_size=bucket)
    ids = np.arange(0, len(kept), max(1, len(kept) // 100))
    rows = np.asarray(fc.extract(jnp.asarray(ids)))
    for i, row in zip(ids, rows):
        got = bytes(row[row != 0])
        assert got == kept[i].encode()[: fc.max_chars], i


def test_front_coding_locate(built):
    _, kept, _ = built
    fc = FrontCodedStore.build(kept, bucket_size=16)
    sample_idx = np.arange(0, len(kept), max(1, len(kept) // 40))
    chars = encode_strings([kept[i] for i in sample_idx], fc.max_chars)
    got = np.asarray(fc.locate(jnp.asarray(chars)))
    assert (got == sample_idx).all()


def test_front_coding_locate_prefix(built):
    _, kept, _ = built
    import bisect
    fc = FrontCodedStore.build(kept, bucket_size=16)
    rng = np.random.default_rng(1)
    prefixes = [kept[i][: rng.integers(1, 8)] for i in rng.integers(0, len(kept), 20)]
    chars = encode_strings(prefixes, fc.max_chars)
    lens = jnp.asarray([len(p) for p in prefixes], jnp.int32)
    l, r = fc.locate_prefix(jnp.asarray(chars), lens)
    for p, li, ri in zip(prefixes, np.asarray(l), np.asarray(r)):
        assert li == bisect.bisect_left(kept, p), p
        assert ri == bisect.bisect_right(kept, p + "\xff"), p


def test_front_coding_smaller_than_raw(built):
    _, kept, _ = built
    fc = FrontCodedStore.build(kept, bucket_size=16)
    raw = sum(len(s) + 1 for s in kept)
    assert fc.encoded_bytes() < raw


# ---------------------------------------------------------------- RMQ
@given(st.integers(1, 500), st.integers(0, 2**31 - 2), st.data())
@settings(max_examples=30, deadline=None)
def test_rmq_matches_argmin(n, _seed, data):
    rng = np.random.default_rng(_seed % 2**32)
    vals = rng.integers(0, 10_000, n).astype(np.int32)
    rmq = RangeMin.build(vals)
    p = data.draw(st.integers(0, n - 1))
    q = data.draw(st.integers(p, n - 1))
    pos, v = rmq.query(jnp.int32(p), jnp.int32(q))
    assert int(v) == vals[p : q + 1].min()
    assert vals[int(pos)] == int(v)


def test_rmq_topk_matches_sorted():
    rng = np.random.default_rng(3)
    vals = rng.permutation(5_000).astype(np.int32)
    rmq = RangeMin.build(vals)
    for p, q in [(0, 5000), (10, 11), (100, 2000), (4990, 5000), (7, 7)]:
        got, _ = topk_in_range(rmq, jnp.int32(p), jnp.int32(q), 10)
        want = np.sort(vals[p:q])[:10]
        want = np.pad(want.astype(np.int64), (0, 10 - len(want)),
                      constant_values=INF_DOCID)
        np.testing.assert_array_equal(np.asarray(got, np.int64), want)


# ---------------------------------------------------------------- engines vs oracle
def _term_range(qidx, suffix: str):
    chars = encode_strings([suffix], qidx.dictionary.max_chars)
    l, r = qidx.dictionary.locate_prefix(
        jnp.asarray(chars), jnp.asarray([len(suffix)], jnp.int32))
    return int(l[0]), int(r[0])


def test_conjunctive_multi_vs_oracle(built):
    qidx, kept, host = built
    rng = np.random.default_rng(7)
    checked = 0
    for qi in rng.integers(0, len(kept), 60):
        toks = kept[qi].split()
        if len(toks) < 2:
            continue
        cut = rng.integers(1, len(toks[-1]) + 1)
        suffix = toks[-1][:cut]
        pids, plen, pok, suf, slen = parse_queries(qidx.dictionary, [" ".join(toks[:-1] + [suffix])])
        tl, tr = qidx.dictionary.locate_prefix(suf, slen)
        got = conjunctive_multi(qidx.index, qidx.completions, pids[0], plen[0],
                                tl[0], tr[0], 10)
        got = [int(x) for x in np.asarray(got) if x != INF_DOCID]
        prefix = [int(x) for x in np.asarray(pids[0]) if x]
        want = host.fwd_conjunctive(prefix, int(tl[0]), int(tr[0]), 10)
        assert got == want, (kept[qi], suffix)
        want_heap = host.heap_conjunctive(prefix, int(tl[0]), int(tr[0]), 10)
        assert got == want_heap
        checked += 1
    assert checked >= 20


def test_single_term_vs_oracle(built):
    qidx, kept, host = built
    rng = np.random.default_rng(11)
    terms = sorted({t for q in kept for t in q.split()})
    for t in [terms[i] for i in rng.integers(0, len(terms), 40)]:
        for cut in (1, 2, len(t)):
            suffix = t[:cut]
            tl, tr = _term_range(qidx, suffix)
            got = single_term_topk(qidx.index, qidx.rmq_minimal,
                                   jnp.int32(tl), jnp.int32(tr), 10)
            got = [int(x) for x in np.asarray(got) if x != INF_DOCID]
            want = host.single_term_rmq(tl, tr, 10)
            assert got == want, (suffix, tl, tr)
            assert want == host.single_term_classic(tl, tr, 10)


def test_prefix_search_vs_oracle(built):
    qidx, kept, host = built
    rng = np.random.default_rng(13)
    for qi in rng.integers(0, len(kept), 50):
        toks = kept[qi].split()
        cut = rng.integers(1, len(toks[-1]) + 1)
        partial = " ".join(toks[:-1] + [toks[-1][:cut]])
        pids, plen, pok, suf, slen = parse_queries(qidx.dictionary, [partial])
        if not pok[0]:
            continue
        tl, tr = qidx.dictionary.locate_prefix(suf, slen)
        got = prefix_search_topk(qidx.completions, qidx.rmq_docids,
                                 pids[0], plen[0], tl[0], tr[0], 10)
        got = [int(x) for x in np.asarray(got) if x != INF_DOCID]
        prefix = [int(x) for x in np.asarray(pids[0]) if x]
        want = host.brute_prefix_search(prefix, int(tl[0]), int(tr[0]), 10)
        assert got == want, partial


def test_conjunctive_superset_of_prefix(built):
    """Paper §3.1 claim: conjunctive-search subsumes prefix-search results."""
    qidx, kept, host = built
    rng = np.random.default_rng(17)
    for qi in rng.integers(0, len(kept), 40):
        toks = kept[qi].split()
        if len(toks) < 2:
            continue
        partial = " ".join(toks[:-1] + [toks[-1][:1]])
        pids, plen, pok, suf, slen = parse_queries(qidx.dictionary, [partial])
        tl, tr = qidx.dictionary.locate_prefix(suf, slen)
        prefix = [int(x) for x in np.asarray(pids[0]) if x]
        c = set(host.brute_conjunctive(prefix, int(tl[0]), int(tr[0]), 10**9))
        p = set(host.brute_prefix_search(prefix, int(tl[0]), int(tr[0]), 10**9))
        assert p <= c


# ---------------------------------------------------------------- batched serving path
def test_vmapped_engines_match_single(built):
    qidx, kept, _ = built
    rng = np.random.default_rng(23)
    partials = []
    for qi in rng.integers(0, len(kept), 16):
        toks = kept[qi].split()
        cut = rng.integers(1, len(toks[-1]) + 1)
        partials.append(" ".join(toks[:-1] + [toks[-1][:cut]]))
    pids, plen, pok, suf, slen = parse_queries(qidx.dictionary, partials)
    tl, tr = qidx.dictionary.locate_prefix(suf, slen)
    batched = jax.vmap(
        lambda a, b, c, d: conjunctive_multi(qidx.index, qidx.completions, a, b, c, d, 10)
    )(pids, plen, tl, tr)
    for i in range(len(partials)):
        single = conjunctive_multi(qidx.index, qidx.completions, pids[i], plen[i],
                                   tl[i], tr[i], 10)
        np.testing.assert_array_equal(np.asarray(batched[i]), np.asarray(single))


def test_hyb_baseline_matches_fwd(built):
    """Bast-Weber HYB engine returns the same results as Fwd/oracle."""
    from repro.core.ref_engines import HybIndex
    qidx, kept, host = built
    hyb = HybIndex(host, c=1e-2)
    rng = np.random.default_rng(31)
    checked = 0
    for qi in rng.integers(0, len(kept), 30):
        toks = kept[qi].split()
        cut = rng.integers(1, len(toks[-1]) + 1)
        partial = " ".join(toks[:-1] + [toks[-1][:cut]])
        pids, plen, pok, suf, slen = parse_queries(qidx.dictionary, [partial])
        tl, tr = qidx.dictionary.locate_prefix(suf, slen)
        prefix = [int(x) for x in np.asarray(pids[0]) if x]
        got = hyb.conjunctive(prefix, int(tl[0]), int(tr[0]), 10)
        if prefix:
            want = host.fwd_conjunctive(prefix, int(tl[0]), int(tr[0]), 10)
        else:
            want = host.single_term_rmq(int(tl[0]), int(tr[0]), 10)
        assert got == want, partial
        checked += 1
    assert checked >= 20


# ---------------------------------------------------------------- corpus pass
_CORPUS_CASES = {
    "generator": (*_mini_corpus(seed=3, n=600, vocab=150), "bulk"),
    "duplicates": (["b a", "b a", "a", "a", "c d e", "c  d e", "a", "b a"],
                   [1.0, 3.0, 2.0, 2.0, 5.0, 7.0, 2.0, 3.0], "bulk"),
    "whitespace": (["\ta \x0b\x0cb\r\n", " ", "", "\x1c\x1d\x1e\x1fc  d ",
                    "a b", "  a\tb  ", "\n", "c d"],
                   [1.0, 2.0, 3.0, 4.0, 0.5, 9.0, 1.0, 2.0], "bulk"),
    "max_terms": (["a b c d e f g h", "a b c d e f g h i", "x",
                   " ".join("abcdefghi")], [1.0, 2.0, 3.0, 4.0], "bulk"),
    "long_terms": (["abcdefghijklmnopqrstuvwxyz0123 q",
                    "abcdefghijklmnopqrstuvwxyz0124",
                    "abcdefghijklmnopqrstuvwx", "q abcdefghijklmnopqrstuvwxyz"],
                   [1.0, 2.0, 3.0, 4.0], "bulk"),
    "short_terms": (["a", "a b", "ab", "a!", "!", "a !"],
                    [1.0, 2.0, 3.0, 4.0, 5.0, 6.0], "bulk"),
    "non_ascii": (["café au lait", "cafe au lait", "b"], [1.0, 2.0, 3.0],
                  "loop"),
    "nul": (["a\x00b c", "a b"], [1.0, 2.0], "loop"),
    "control": (["a\x07b c", "a b"], [1.0, 2.0], "loop"),
}


@pytest.mark.parametrize("case", sorted(_CORPUS_CASES))
def test_corpus_pass_matches_the_loop(case):
    """``build_corpus`` (the whole-array pass where the input allows it)
    against the per-query loop, and the index built from each."""
    from repro.core.builder import _corpus_loop, index_from_corpus
    from repro.obs.tracing import build_stages
    qs, sc, path = _CORPUS_CASES[case]
    got = build_corpus(qs, sc)
    assert build_stages()[-1][0] == "build.corpus"
    assert build_stages()[-1][3] == {"path": path}
    want = _corpus_loop(qs, sc, 8, 24)
    (gd, g_rows, g_sc, g_kept), (wd, w_rows, w_sc, w_kept) = got, want
    assert gd.n_terms == wd.n_terms and gd.max_chars == wd.max_chars
    np.testing.assert_array_equal(np.asarray(gd.chars), np.asarray(wd.chars))
    np.testing.assert_array_equal(np.asarray(gd.keys), np.asarray(wd.keys))
    assert g_rows.dtype == w_rows.dtype and g_sc.dtype == w_sc.dtype
    np.testing.assert_array_equal(g_rows, w_rows)
    np.testing.assert_array_equal(g_sc.view(np.int64), w_sc.view(np.int64))
    assert g_kept == w_kept and all(type(k) is str for k in g_kept)
    qidx, kept, _ = build_qac_index(qs, sc)
    ref = index_from_corpus(wd, w_rows, w_sc)
    assert kept == w_kept
    g_leaves, g_tree = jax.tree_util.tree_flatten(qidx)
    w_leaves, w_tree = jax.tree_util.tree_flatten(ref)
    assert g_tree == w_tree
    for a, b in zip(g_leaves, w_leaves):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_corpus_pass_keeps_the_first_of_equal_best_scores():
    """Signed zeros compare equal: the first query of the best score wins,
    as in the loop."""
    _, _, sc, kept = build_corpus(["a", "a", "b", "b"], [0.0, -0.0, -0.0, 0.0])
    assert kept == ["a", "b"]
    assert np.signbit(sc).tolist() == [False, True]


def test_build_stages_tile_the_build():
    from repro.obs.tracing import build_stages
    qs, sc = _mini_corpus(seed=4, n=300, vocab=90)
    build_qac_index(qs, sc)
    recs = build_stages()[-5:]
    assert [r[0] for r in recs] == ["build.corpus", "build.completions",
                                    "build.postings", "build.pack",
                                    "build.rmq"]
    assert recs[0][3] == {"path": "bulk"}
    for (_, _, end, _), (_, start, _, _) in zip(recs, recs[1:]):
        assert start == end
    assert all(t1 >= t0 for _, t0, t1, _ in recs)
