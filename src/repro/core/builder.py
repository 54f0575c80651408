"""Corpus -> QACIndex: ties every structure of paper §3.2 together."""
from __future__ import annotations

import dataclasses
import itertools
from typing import Optional, Sequence

import numpy as np
import jax.numpy as jnp

from .types import MAX_TERMS, MAX_TERM_CHARS, pytree_dataclass
from .dictionary import TermDictionary
from .fc import FrontCodedStore
from .completions import Completions
from .inverted_index import InvertedIndex
from .rmq import RangeMin
from .strings import encode_strings
from ..obs.tracing import build_clock, build_stage


@pytree_dataclass(meta_fields=("k_default",))
class QACIndex:
    dictionary: TermDictionary
    completions: Completions
    index: InvertedIndex
    rmq_docids: RangeMin        # over completions.docids (prefix-search top-k)
    rmq_minimal: RangeMin       # over index.minimal (single-term queries)
    k_default: int


@dataclasses.dataclass
class CorpusStats:
    n_queries: int
    n_unique_terms: int
    avg_chars_per_term: float
    avg_queries_per_term: float
    avg_terms_per_query: float
    uncompressed_bytes: int


def tokenize(s: str) -> list[str]:
    return s.split()   # splits on whitespace runs, drops empty tokens


# Byte classes of the bulk corpus pass: a token byte, a space, another
# whitespace byte of ``str.split()`` (ASCII), and any other byte, which sends
# the input to the per-query loop.
_TOKEN, _SPACE, _WS, _OTHER = 0, 1, 2, 3
_BYTE_CLASS = np.full(256, _OTHER, np.uint8)
_BYTE_CLASS[0x21:0x80] = _TOKEN
_BYTE_CLASS[list(b"\t\n\x0b\x0c\r\x1c\x1d\x1e\x1f")] = _WS
_BYTE_CLASS[0x20] = _SPACE
_BULK_MAX_TOKEN = 64           # longest token the bulk pass keys, in bytes
_PAD = _BULK_MAX_TOKEN + 16    # whitespace after the last query
# _KEEP_MASK[b]: the first b bytes of a big-endian uint64
_KEEP_MASK = np.array([((1 << 64) - 1) ^ ((1 << (64 - 8 * b)) - 1)
                       for b in range(9)], np.uint64)


def build_corpus(queries: Sequence[str], scores: Sequence[float],
                 max_terms: int = MAX_TERMS,
                 max_term_chars: int = MAX_TERM_CHARS):
    """Dedup + tokenize a scored query log (host side).

    Returns (dictionary, term_rows int32[N,M], scores float64[N],
    kept_strings): the distinct token sequences of 1 to ``max_terms``
    tokens, each under its highest score (the first of equal ones), sorted
    as their single-space joined strings, with 1-based lexicographic term
    ids. An ASCII log whose only bytes below 0x21 are whitespace, with no
    NaN score and no token over 64 bytes, takes a whole-array pass; any
    other takes the per-query loop. Both give the same result.
    """
    with build_stage("build.corpus") as st:
        out = _corpus_bulk(queries, scores, max_terms, max_term_chars)
        st.attrs["path"] = "loop" if out is None else "bulk"
        if out is None:
            out = _corpus_loop(queries, scores, max_terms, max_term_chars)
        return out


def _corpus_loop(queries, scores, max_terms, max_term_chars):
    """``build_corpus`` one query and one token at a time: any input."""
    seen = {}
    get = seen.get
    for q, s in zip(queries, scores):
        toks = q.split()
        if not toks or len(toks) > max_terms:
            continue
        key = " ".join(toks)
        s = float(s)
        old = get(key)
        if old is None or s > old:
            seen[key] = s
    kept = sorted(seen)
    sc = np.fromiter((seen[kq] for kq in kept), dtype=np.float64,
                     count=len(kept))
    toks = [q.split() for q in kept]
    vocab = sorted({t for ts in toks for t in ts})
    dictionary = TermDictionary.build(vocab, max_term_chars)
    tid = {t: i + 1 for i, t in enumerate(vocab)}  # 1-based lexicographic ids
    lens = np.fromiter(map(len, toks), dtype=np.int64, count=len(kept))
    flat = np.fromiter((tid[t] for ts in toks for t in ts), dtype=np.int32,
                       count=int(lens.sum()))
    rows = np.zeros((len(kept), max_terms), dtype=np.int32)
    starts = np.repeat(np.cumsum(lens) - lens, lens)
    rows[np.repeat(np.arange(len(kept)), lens),
         np.arange(flat.size) - starts] = flat
    return dictionary, rows, sc, kept


def _corpus_bulk(queries, scores, max_terms, max_term_chars):
    """``build_corpus`` in whole-array passes over the queries joined into
    one byte buffer; None when the input does not allow it.

    Token bytes are at least 0x21 and sort above the single space that
    joins a kept string's tokens, so the order of the joined strings is the
    order of their 0-padded term-id rows, and byte order is ``str`` order.
    """
    n = len(queries)
    sc_in = np.asarray(scores, dtype=np.float64)
    if n == 0 or sc_in.shape != (n,) or np.isnan(sc_in).any():
        return None
    text = "\n".join(itertools.chain(queries, ["\n" * (_PAD - 1)]))
    if not text.isascii():
        return None
    buf = np.frombuffer(text.encode("ascii"), np.uint8)
    del text
    cls = _BYTE_CLASS[buf]
    if (cls == _OTHER).any():
        return None
    edge = np.diff((cls == _TOKEN).view(np.int8), prepend=np.int8(0))
    starts = np.flatnonzero(edge == 1)
    lens = np.flatnonzero(edge == -1) - starts
    del edge
    qlen = np.fromiter(map(len, queries), np.int64, count=n)
    qoff = np.cumsum(qlen + 1) - (qlen + 1)
    q_of = np.searchsorted(qoff, starts, side="right") - 1
    cnt = np.bincount(q_of, minlength=n)
    ok = (cnt > 0) & (cnt <= max_terms)
    keep = ok[q_of]
    starts, lens, q_of = starts[keep], lens[keep], q_of[keep]
    if not starts.size or lens.max() > _BULK_MAX_TOKEN:
        return None

    # vocabulary: sort the tokens by big-endian 8-byte keys of their bytes
    keys = _byte_keys(buf, starts, lens, int(lens.max()))
    order = np.lexsort(keys[::-1])
    new = _run_starts(keys, order)
    del keys
    tid = np.empty(order.size, np.int32)
    tid[order] = np.cumsum(new, dtype=np.int32)   # 1-based lexicographic ids
    first_tok = order[new]
    del order, new

    # one term-id row per query, then its distinct rows under their best
    # score: sorted by (row, -score, position), the first of each row
    kq = np.flatnonzero(ok)
    c = cnt[kq]
    rows_q = np.zeros((kq.size, max_terms), np.int32)
    col = np.arange(tid.size) - np.repeat(np.cumsum(c) - c, c)
    rows_q[np.repeat(np.arange(kq.size), c), col] = tid
    del col, tid
    bits = int(first_tok.size).bit_length()
    per = 64 // bits
    words = []          # the rows, ``per`` ids to a word; all-zero words skipped
    for j0 in range(0, max_terms, per):
        w = np.zeros(kq.size, np.uint64)
        for j in range(j0, min(j0 + per, max_terms)):
            w = (w << np.uint64(bits)) | rows_q[:, j].astype(np.uint64)
        if w.any():
            words.append(w)
    sc_q = sc_in[kq]
    order = np.lexsort([-sc_q] + words[::-1])
    best = order[_run_starts(words, order)]
    del words
    rows, sc = rows_q[best], sc_q[best]
    del rows_q, order

    # kept strings: a query already in its joined form is its own kept
    # string (one space between tokens, none around them)
    tok_bytes = np.bincount(q_of, weights=lens, minlength=n)
    joined = qlen == tok_bytes + cnt - 1
    other_ws = np.flatnonzero(cls == _WS)
    q_ws = np.searchsorted(qoff, other_ws, side="right") - 1
    joined[q_ws[other_ws < qoff[q_ws] + qlen[q_ws]]] = False
    kept = list(map(queries.__getitem__, kq[best].tolist()))
    for i in np.flatnonzero(~joined[kq[best]]).tolist():
        kept[i] = " ".join(kept[i].split())

    # the dictionary: each term's bytes, cut to max_term_chars
    chars = np.stack(_byte_keys(buf, starts[first_tok],
                                np.minimum(lens[first_tok], max_term_chars),
                                max_term_chars), axis=1)
    chars = chars.astype(">u8").view(np.uint8)[:, :max_term_chars]
    dictionary = TermDictionary.from_chars(np.ascontiguousarray(chars),
                                           max_term_chars)
    return dictionary, rows, sc, kept


def _run_starts(keys, order):
    """Where each run of equal rows of ``keys`` starts, in ``order``."""
    new = np.zeros(order.size, bool)
    new[0] = True
    for key in keys:
        k = key[order]
        new[1:] |= k[1:] != k[:-1]
    return new


def _byte_keys(buf, starts, lens, width):
    """The bytes ``buf[s:s + l]`` of each (start, length), 0-padded to
    ``width``, as big-endian uint64 words: sorting by the words in turn
    sorts the byte strings. ``buf`` holds ``_PAD`` bytes past the last."""
    windows = np.ndarray((buf.size - 7,), ">u8", buf, strides=(1,))
    last = buf.size - 8
    return [windows[np.minimum(starts + 8 * k, last)].astype(np.uint64)
            & _KEEP_MASK[np.clip(lens - 8 * k, 0, 8)]
            for k in range(-(-width // 8))]


def docid_of_rows(rows: np.ndarray, scores: np.ndarray) -> np.ndarray:
    """Row -> docid: the rank under (-score, lexicographic term row), the
    paper's decreasing-score assignment with lexicographic tie-break."""
    order = np.lexsort(
        tuple(rows[:, j] for j in range(rows.shape[1] - 1, -1, -1)) + (-scores,)
    )
    d_of_row = np.empty(len(rows), dtype=np.int32)
    d_of_row[order] = np.arange(len(rows), dtype=np.int32)
    return d_of_row


def index_from_corpus(dictionary: TermDictionary, rows: np.ndarray,
                      sc: np.ndarray, k_default: int = 10,
                      postings_codec: str | None = "ef") -> QACIndex:
    """``build_corpus`` output -> QACIndex (the device half of the build)."""
    with build_stage("build.completions"):
        comps = Completions.build(rows, sc)
    inv = InvertedIndex.build(rows, docid_of_rows(rows, sc),
                              dictionary.n_terms,
                              postings_codec=postings_codec)
    with build_stage("build.rmq"):
        rmq_docids = RangeMin.build(np.asarray(comps.docids))
        rmq_minimal = inv.build_minimal_rmq()
    return QACIndex(
        dictionary=dictionary,
        completions=comps,
        index=inv,
        rmq_docids=rmq_docids,
        rmq_minimal=rmq_minimal,
        k_default=k_default,
    )


def build_qac_index(queries: Sequence[str], scores: Sequence[float],
                    k_default: int = 10,
                    max_terms: int = MAX_TERMS,
                    max_term_chars: int = MAX_TERM_CHARS,
                    postings_codec: str | None = "ef"):
    """Full pipeline: scored log -> all paper data structures.

    ``postings_codec`` ("ef" default, "bitpack", or None) controls the
    compressed device layout emitted alongside raw CSR (see
    ``InvertedIndex.build``); serving routes pick raw or packed per the
    VMEM gate (``core.search`` ``postings_codec`` knob).
    """
    with build_clock():
        dictionary, rows, sc, kept = build_corpus(
            queries, scores, max_terms, max_term_chars
        )
        qidx = index_from_corpus(dictionary, rows, sc, k_default,
                                 postings_codec=postings_codec)
    return qidx, kept, sc


def corpus_stats(kept: Sequence[str]) -> CorpusStats:
    terms = [t for q in kept for t in tokenize(q)]
    uniq = set(terms)
    return CorpusStats(
        n_queries=len(kept),
        n_unique_terms=len(uniq),
        avg_chars_per_term=float(np.mean([len(t) for t in uniq])) if uniq else 0.0,
        avg_queries_per_term=len(terms) / max(len(uniq), 1),
        avg_terms_per_query=len(terms) / max(len(kept), 1),
        uncompressed_bytes=sum(len(q) + 1 for q in kept),
    )


def parse_queries(dictionary: TermDictionary, raw_queries: Sequence[str],
                  max_terms: int = MAX_TERMS,
                  max_term_chars: int = MAX_TERM_CHARS):
    """Paper §3.1 "Parsing": split each raw query into prefix term-ids and a
    (possibly incomplete) suffix. Host-side; returns device-ready arrays.

    A trailing space means the last term is complete -> it joins the prefix
    and the suffix is empty (matches any term).
    """
    B = len(raw_queries)
    prefix_ids = np.zeros((B, max_terms), dtype=np.int32)
    prefix_len = np.zeros(B, dtype=np.int32)
    prefix_ok = np.ones(B, dtype=bool)
    suffix = np.zeros((B, max_term_chars), dtype=np.uint8)
    suffix_len = np.zeros(B, dtype=np.int32)
    all_terms = []
    for q in raw_queries:
        toks = tokenize(q)
        ends_complete = q.endswith(" ") or q.endswith("\t")
        pre = toks if ends_complete else toks[:-1]
        all_terms.append((pre, "" if ends_complete or not toks else toks[-1]))
    flat = [t for pre, _ in all_terms for t in pre]
    ids = {}
    if flat:
        uniq = sorted(set(flat))
        chars = encode_strings(uniq, max_term_chars)
        got = np.asarray(dictionary.locate(jnp.asarray(chars)))
        ids = dict(zip(uniq, got.tolist()))
    for i, (pre, suf) in enumerate(all_terms):
        pre = pre[: max_terms - 1]
        for j, t in enumerate(pre):
            tid = ids.get(t, 0)
            prefix_ids[i, j] = tid
            if tid == 0:
                prefix_ok[i] = False
        prefix_len[i] = len(pre)
        b = suf.encode("utf-8")[:max_term_chars]
        suffix[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
        suffix_len[i] = len(b)
    return (
        jnp.asarray(prefix_ids),
        jnp.asarray(prefix_len),
        np.asarray(prefix_ok),
        jnp.asarray(suffix),
        jnp.asarray(suffix_len),
    )
