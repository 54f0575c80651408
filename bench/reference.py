"""Plain reference for a QAC deployment: exact top-k by brute force.

Independent of the program: it imports nothing from ``src/`` and works from
the term ids that the benchmark's own log generator drew. Semantics, as the
configuration states them (paper arXiv:2005.06213, §3.1):

- The log is deduplicated by token sequence, keeping the highest score;
  queries longer than ``max_terms`` terms are dropped.
- Completions rank by decreasing score, ties by their token sequence in
  lexicographic order; a completion's rank is its docid.
- A raw query splits into prefix terms (every complete term, at most
  ``max_terms - 1``) and a suffix (the last, incomplete term; empty when
  the query ends in a space), cut to ``max_term_chars`` bytes.
- The answer is the k best-ranked completions that hold every prefix term
  and at least one term starting with the suffix, padded with ``INF``.
"""
from __future__ import annotations

import bisect

import numpy as np

INF = 2**31 - 1
CHUNK = 1 << 18          # candidates checked per step; stops at k matches


class ExactTopK:
    def __init__(self, vocab: list, term_idx: np.ndarray, n_terms: np.ndarray,
                 scores: np.ndarray, *, max_terms: int, max_term_chars: int):
        self.vocab = vocab
        self.max_terms = max_terms
        self.max_term_chars = max_term_chars
        n_terms = np.asarray(n_terms, np.int64)
        width = int(n_terms.max())
        rows = np.zeros((n_terms.size, width), np.int32)
        starts = np.repeat(np.cumsum(n_terms) - n_terms, n_terms)
        rows[np.repeat(np.arange(n_terms.size), n_terms),
             np.arange(starts.size) - starts] = np.asarray(term_idx) + 1
        keep = n_terms <= max_terms
        rows, scores = rows[keep], np.asarray(scores, np.float64)[keep]
        # group equal token sequences, the best score first in each group
        order = np.lexsort((-scores,) + tuple(
            rows[:, j] for j in range(width - 1, -1, -1)))
        rows, scores = rows[order], scores[order]
        first = np.ones(len(rows), bool)
        first[1:] = (rows[1:] != rows[:-1]).any(axis=1)
        rows, scores = rows[first], scores[first]
        # rows are in token order now: a stable sort by score breaks ties so
        rank = np.argsort(-scores, kind="stable")
        self.rows = np.ascontiguousarray(rows[rank])      # docid order
        # term -> ascending docids that hold it (a docid twice when its
        # completion repeats the term)
        flat = self.rows.ravel()
        held = flat > 0
        docs = np.repeat(np.arange(len(self.rows), dtype=np.int32),
                         width)[held]
        terms = flat[held]
        by_term = np.argsort(terms, kind="stable")
        self.postings = docs[by_term]
        self.offsets = np.zeros(len(vocab) + 2, np.int64)
        np.cumsum(np.bincount(terms, minlength=len(vocab) + 1),
                  out=self.offsets[1:])

    def __len__(self) -> int:
        return len(self.rows)

    def completion(self, docid: int) -> str:
        return " ".join(self.vocab[t - 1] for t in self.rows[docid] if t)

    def _term_id(self, term: str) -> int:
        i = bisect.bisect_left(self.vocab, term)
        return i + 1 if i < len(self.vocab) and self.vocab[i] == term else 0

    def parse(self, query: str):
        """-> (prefix term ids, [lo, hi) range of suffix term ids)."""
        toks = query.split()
        complete = query.endswith((" ", "\t"))
        pre = (toks if complete else toks[:-1])[: self.max_terms - 1]
        suf = "" if complete or not toks else toks[-1]
        suf = suf.encode()[: self.max_term_chars].decode(errors="ignore")
        ids = [self._term_id(t) for t in pre]
        if not suf:
            return ids, 1, len(self.vocab) + 1
        nxt = suf[:-1] + chr(ord(suf[-1]) + 1)
        lo = bisect.bisect_left(self.vocab, suf) + 1
        hi = bisect.bisect_left(self.vocab, nxt) + 1
        return ids, lo, hi

    def _matches(self, docids: np.ndarray, ids, lo: int, hi: int):
        """The docids, in order, whose completion holds every prefix term
        and a term in [lo, hi)."""
        rows = self.rows[docids]
        m = ((rows >= lo) & (rows < hi)).any(axis=1)
        for t in set(ids):
            m &= (rows == t).any(axis=1)
        return docids[m]

    def answer(self, query: str, k: int, n_rows: int | None = None):
        """-> int32[k] docids. ``n_rows`` keeps only the best ``n_rows``
        completions: the control, which breaks the exact top-k."""
        ids, lo, hi = self.parse(query)
        out = np.full(k, INF, np.int32)
        if 0 in ids or lo >= hi:
            return out
        n = len(self.rows) if n_rows is None else n_rows
        if ids:
            # every match holds the rarest prefix term: walk its list
            t = min(set(ids), key=lambda t: self.offsets[t + 1]
                    - self.offsets[t])
            cand = np.unique(self.postings[self.offsets[t]:
                                           self.offsets[t + 1]])
            cand = cand[cand < n]
        elif self.offsets[hi] - self.offsets[lo] <= CHUNK:
            cand = np.unique(self.postings[self.offsets[lo]:
                                           self.offsets[hi]])
            cand = cand[cand < n]
        else:
            cand = None        # a dense range: scan completions in order
        found = []
        for c0 in range(0, n if cand is None else len(cand), CHUNK):
            docids = (np.arange(c0, min(c0 + CHUNK, n), dtype=np.int32)
                      if cand is None else cand[c0:c0 + CHUNK])
            found.extend(self._matches(docids, ids, lo, hi)[
                : k - len(found)].tolist())
            if len(found) == k:
                break
        out[: len(found)] = found
        return out
