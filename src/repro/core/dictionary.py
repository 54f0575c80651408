"""The term dictionary (paper §3.2 "The Dictionary").

TPU-native representation: lexicographically sorted, padded char matrix plus
packed int32 chunk keys. Locate / LocatePrefix are batched binary searches;
Extract is a row gather. The Front-Coded variant (space/time study, paper
Table 3) lives in ``fc.py``.

Term ids are 1-based lexicographic ranks (0 = PAD), exactly the paper's
"lexicographic integer id".
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .types import MAX_TERM_CHARS, pytree_dataclass
from .strings import encode_strings, pack_chars, prefix_bound_keys, n_chunks
from .searching import ranged_searchsorted_keys


@pytree_dataclass(meta_fields=("n_terms", "max_chars"))
class TermDictionary:
    chars: jnp.ndarray      # uint8[V, T] sorted
    keys: jnp.ndarray       # int32[V, C] packed chunk keys
    n_terms: int
    max_chars: int

    # -- construction -------------------------------------------------------
    @staticmethod
    def build(terms, max_chars: int = MAX_TERM_CHARS) -> "TermDictionary":
        """terms: iterable of unique strings (host side)."""
        return TermDictionary.from_chars(
            encode_strings(sorted(set(terms)), max_chars), max_chars)

    @staticmethod
    def from_chars(chars: np.ndarray,
                   max_chars: int = MAX_TERM_CHARS) -> "TermDictionary":
        """chars: uint8[V, max_chars], the sorted terms' bytes, 0-padded."""
        return TermDictionary(
            chars=jnp.asarray(chars),
            keys=jnp.asarray(pack_chars(chars)),
            n_terms=len(chars),
            max_chars=max_chars,
        )

    # -- queries (all jit/vmap friendly) ------------------------------------
    def locate(self, q_chars: jnp.ndarray) -> jnp.ndarray:
        """Locate(t): uint8[B, T] -> 1-based term id, 0 if absent."""
        q_keys = pack_chars(q_chars)

        def one(qk, qc):
            lo = jnp.int32(0)
            hi = jnp.int32(self.n_terms)
            pos = ranged_searchsorted_keys(self.keys, qk, lo, hi, side="left")
            row = self.chars[jnp.minimum(pos, self.n_terms - 1)]
            hit = (pos < self.n_terms) & jnp.all(row == qc)
            return jnp.where(hit, pos + 1, 0).astype(jnp.int32)

        return jax.vmap(one)(q_keys, q_chars)

    def locate_prefix(self, q_chars: jnp.ndarray, q_len: jnp.ndarray):
        """LocatePrefix(suffix): -> (l, r) 1-based half-open term-id range.

        Empty range (no term has the prefix) gives l == r.
        A zero-length prefix matches every term: (1, V+1).
        """
        lo_keys, hi_keys = prefix_bound_keys(q_chars, q_len, self.max_chars)

        def one(lk, hk):
            z = jnp.int32(0)
            v = jnp.int32(self.n_terms)
            l = ranged_searchsorted_keys(self.keys, lk, z, v, side="left")
            r = ranged_searchsorted_keys(self.keys, hk, z, v, side="right")
            return l + 1, r + 1  # to 1-based ids

        return jax.vmap(one)(lo_keys, hi_keys)

    def extract(self, term_ids: jnp.ndarray) -> jnp.ndarray:
        """Extract(id): 1-based ids[B] -> uint8[B, T] (PAD id -> zeros)."""
        idx = jnp.clip(term_ids - 1, 0, self.n_terms - 1)
        rows = self.chars[idx]
        return jnp.where((term_ids > 0)[:, None], rows, 0).astype(jnp.uint8)

    # -- host helpers --------------------------------------------------------
    def id_of(self, term: str) -> int:
        """Host-side exact lookup (for builders/tests)."""
        chars = encode_strings([term], self.max_chars)
        return int(self.locate(jnp.asarray(chars))[0])

    def space_bytes(self) -> int:
        return int(self.chars.nbytes + self.keys.nbytes)

    @property
    def n_key_chunks(self) -> int:
        return n_chunks(self.max_chars)
