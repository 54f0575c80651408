"""The inverted index (paper §3.2) in CSR form + the ``minimal`` array.

Lists are docid-ascending == score-descending (the paper's invariant), so
"first k" == "top-k". NextGeq is a ranged binary search. The `minimal`
array (first docid of every list) feeds the single-term RMQ algorithm
(paper §3.3). ``packed`` optionally carries the same postings in the
device block format (``codecs.PackedPostings``) so the fused kernels can
decode on-chip — the raw CSR arrays stay authoritative (XLA/off-TPU
reference); the two are interchangeable by the
``unpack_postings(packed) == postings`` contract the builder asserts.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .types import INF_DOCID, pytree_dataclass
from .searching import ranged_searchsorted, next_geq
from .codecs import PackedPostings, pack_postings, unpack_postings
from .rmq import RangeMin
from ..obs.tracing import build_stage


@pytree_dataclass(meta_fields=("n_terms", "n_postings"))
class InvertedIndex:
    postings: jnp.ndarray    # int32[P] concatenated docid lists (ascending)
    offsets: jnp.ndarray     # int32[V+2] list boundaries, indexed by 1-based term id
    minimal: jnp.ndarray     # int32[V+2] first docid per list (INF if empty)
    n_terms: int
    n_postings: int
    packed: PackedPostings | None = None   # device block format (optional)

    @staticmethod
    def build(term_rows: np.ndarray, docid_of_row: np.ndarray, n_terms: int,
              postings_codec: str | None = "ef"):
        """term_rows int32[N, M] (1-based ids, 0 pad); docid_of_row int32[N].

        ``postings_codec``: "ef" (default) or "bitpack" additionally emits
        the compressed device layout into ``.packed``; None skips it.
        """
        with build_stage("build.postings"):
            d, offsets, minimal = _csr(term_rows, docid_of_row, n_terms)
            postings = jnp.asarray(d.astype(np.int32))
        packed = None
        if postings_codec is not None:
            with build_stage("build.pack"):
                packed = pack_postings(d.astype(np.int32), postings_codec)
                got = unpack_postings(packed)
                assert (got == d).all(), "packed postings round-trip broke"
        return InvertedIndex(
            postings=postings,
            offsets=jnp.asarray(offsets),
            minimal=jnp.asarray(minimal),
            n_terms=n_terms,
            n_postings=len(d),
            packed=packed,
        )

    # -- primitives -----------------------------------------------------------
    def list_bounds(self, term_id):
        t = jnp.clip(term_id, 0, self.n_terms)
        return self.offsets[t], self.offsets[t + 1]

    def list_len(self, term_id):
        s, e = self.list_bounds(term_id)
        return e - s

    def next_geq(self, term_id, x):
        s, e = self.list_bounds(term_id)
        val, _ = next_geq(self.postings, s, e, x, INF_DOCID)
        return val

    def contains(self, term_id, x):
        return self.next_geq(term_id, x) == x

    def space_bytes(self) -> int:
        return int(self.postings.nbytes + self.offsets.nbytes)

    def build_minimal_rmq(self) -> RangeMin:
        """RMQ over the minimal array for single-term queries (paper §3.3)."""
        return RangeMin.build(np.asarray(self.minimal))


def _csr(term_rows, docid_of_row, n_terms):
    """(term, docid) pairs -> (postings int64[P], offsets, minimal)."""
    term_rows = np.asarray(term_rows, dtype=np.int64)
    n, m = term_rows.shape
    docs = np.broadcast_to(np.asarray(docid_of_row, dtype=np.int64)[:, None], (n, m))
    mask = term_rows != 0
    t = term_rows[mask]
    d = docs[mask]
    # dedup (term, doc) pairs — a term may repeat inside one completion
    key = t * (np.int64(docid_of_row.max()) + 1) + d
    uniq = np.unique(key)
    t = (uniq // (np.int64(docid_of_row.max()) + 1)).astype(np.int64)
    d = (uniq % (np.int64(docid_of_row.max()) + 1)).astype(np.int64)
    order = np.lexsort((d, t))
    t, d = t[order], d[order]
    cnt = np.bincount(t, minlength=n_terms + 1)  # indexed by 1-based term id
    offsets = np.zeros(n_terms + 2, dtype=np.int32)
    offsets[1 : len(cnt) + 1] = np.cumsum(cnt)
    offsets[len(cnt) + 1 :] = len(d)
    minimal = np.full(n_terms + 2, INF_DOCID, dtype=np.int32)
    starts = offsets[:-1]
    ends = offsets[1:]
    nonempty = ends > starts
    minimal[:-1][nonempty] = d[starts[nonempty]]
    return d, offsets, minimal
