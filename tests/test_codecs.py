"""Codec round-trips + compressed-postings parity (ISSUE 7 satellites).

Every codec is checked two ways: deterministic edge-case sweeps (always run,
CI tier-1) and hypothesis property tests (run when hypothesis is installed,
skip otherwise — see tests/_hyp.py). The block-format tests pin the
compressed-on-chip contract: ``packed_lookup(ptr) == postings[ptr]`` for
every in-bounds pointer, under jit, for both codecs.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hyp import given, settings, st

from repro.core.codecs import (
    BitReader, BitWriter, PACK_BLOCK, PackedPostings, bitpack_bits,
    ef_decode, ef_encode, pack_postings, packed_lookup, pef_bits,
    unpack_postings, vbyte_decode, vbyte_encode,
)


def _sorted_values(rng, n, universe):
    return np.sort(rng.integers(0, universe, size=n).astype(np.int64))


def _csr_like(rng, n_lists, max_len, universe):
    """Concatenated ascending lists — ascending only WITHIN each list."""
    parts = [np.sort(rng.choice(universe, size=rng.integers(1, max_len),
                                replace=False))
             for _ in range(n_lists)]
    return np.concatenate(parts).astype(np.int64)


# ---------------------------------------------------------------- bit I/O
def test_bit_io_roundtrip_mixed():
    rng = np.random.default_rng(0)
    bw = BitWriter()
    fields = []
    for _ in range(200):
        nb = int(rng.integers(0, 48))
        v = int(rng.integers(0, 1 << nb)) if nb else 0
        bw.write(v, nb)
        fields.append((v, nb))
    r = BitReader(bw.array())
    for v, nb in fields:
        assert r.read(nb) == v


def test_bit_io_vectorized_matches_scalar():
    rng = np.random.default_rng(1)
    for nb in (0, 1, 5, 7, 13, 31, 32, 47, 63):
        vals = rng.integers(0, (1 << nb) if nb else 1, size=257)
        bw = BitWriter()
        bw.write_many(vals, nb)
        sw = BitWriter()
        for v in vals:
            sw.write(int(v), nb)
        assert np.array_equal(bw.array(), sw.array())
        got = BitReader(bw.array()).read_many(len(vals), nb)
        assert np.array_equal(got, vals)


def test_unary_many_roundtrip():
    rng = np.random.default_rng(2)
    gaps = rng.integers(0, 9, size=300)
    bw = BitWriter()
    bw.unary_many(gaps)
    assert np.array_equal(BitReader(bw.array()).unary_many(len(gaps)), gaps)


# ---------------------------------------------------------------- ef / vbyte
@pytest.mark.parametrize("n,universe", [(0, 1), (1, 1), (1, 1 << 31),
                                        (127, 1000), (128, 1000),
                                        (129, 10**6), (500, 1 << 31)])
def test_ef_roundtrip_edges(n, universe):
    rng = np.random.default_rng(n + universe % 97)
    v = _sorted_values(rng, n, universe)
    assert np.array_equal(ef_decode(ef_encode(v)), v)


def test_ef_all_equal_and_dense():
    v = np.full(130, 42, dtype=np.int64)
    assert np.array_equal(ef_decode(ef_encode(v)), v)
    v = np.arange(256, dtype=np.int64)
    assert np.array_equal(ef_decode(ef_encode(v)), v)


@pytest.mark.parametrize("n", [0, 1, 3, 100])
def test_vbyte_roundtrip(n):
    rng = np.random.default_rng(n)
    v = _sorted_values(rng, n, 1 << 31)
    assert np.array_equal(vbyte_decode(vbyte_encode(v), n), v)


def test_size_estimators_positive():
    rng = np.random.default_rng(3)
    v = _sorted_values(rng, 1000, 10**6)
    assert pef_bits(v) > 0
    assert bitpack_bits(v) > 0


# ---------------------------------------------------------------- block format
@pytest.mark.parametrize("codec", ["ef", "bitpack"])
@pytest.mark.parametrize("n", [1, 2, 127, 128, 129, 383, 1024])
def test_pack_roundtrip_sizes(codec, n):
    rng = np.random.default_rng(n)
    v = _sorted_values(rng, n, 1 << 20)
    pk = pack_postings(v, codec)
    assert pk.n_post == n
    assert np.array_equal(unpack_postings(pk), v.astype(np.int32))


@pytest.mark.parametrize("codec", ["ef", "bitpack"])
def test_pack_roundtrip_max_universe(codec):
    v = np.array([0, 1, 2**31 - 2, 2**31 - 1] * 40, dtype=np.int64)
    v.sort()
    pk = pack_postings(v, codec)
    assert np.array_equal(unpack_postings(pk), v.astype(np.int32))


def test_pack_roundtrip_unsorted_blocks():
    # CSR concatenation is NOT globally sorted; bitpack must not care and
    # ef must fall back to bitpack payloads for unsorted blocks
    rng = np.random.default_rng(7)
    v = _csr_like(rng, 40, 60, 5000)
    for codec in ("ef", "bitpack"):
        pk = pack_postings(v, codec)
        assert np.array_equal(unpack_postings(pk), v.astype(np.int32))


def _ref_pack(v, codec):
    """Per-block reference coder: one ``BitWriter`` pass over the blocks
    in turn -> (words, base, meta, wordoff) as the format describes."""
    v = np.asarray(v, dtype=np.int64)
    nb = max(1, -(-len(v) // PACK_BLOCK))
    vp = np.full(nb * PACK_BLOCK, v[-1] if len(v) else 0, dtype=np.int64)
    vp[:len(v)] = v
    bw, base, meta, wordoff = BitWriter(), [], [], []
    for blk in vp.reshape(nb, PACK_BLOCK):
        b0 = int(blk.min())
        d = blk - b0
        width = int(d.max()).bit_length()
        low = max(width - 7, 0)
        ef = (codec == "ef" and bool((np.diff(blk) >= 0).all())
              and 8 + 4 * low < 4 * width)
        base.append(b0)
        wordoff.append(bw.n_bits() // 32)
        if ef:
            start = bw.n_bits()
            bw.unary_many(np.diff(d >> low, prepend=np.int64(0)))
            bw.pad_to(start + 256)
            bw.write_many(d & ((1 << low) - 1), low)
            meta.append(low | 64)
        else:
            bw.write_many(d, width)
            meta.append(width)
    n32 = bw.n_bits() // 32
    w64 = bw.array()
    words = np.zeros(max(n32, 1), np.uint32)
    both = np.stack([w64 & 0xFFFFFFFF, w64 >> np.uint64(32)], 1).ravel()
    words[:n32] = both[:n32]
    return words, np.array(base), np.array(meta), np.array(wordoff)


def _ref_unpack(words, base, meta, wordoff, n):
    """Per-block reference decoder (``BitReader``) of ``_ref_pack``."""
    out = []
    for b0, m, off in zip(base, meta, wordoff):
        w, ef = int(m) & 63, int(m) >> 6
        seg = words[off:off + (8 if ef else 0) + 4 * w].astype(np.uint64)
        if len(seg) % 2:
            seg = np.append(seg, np.uint64(0))
        r = BitReader(seg[0::2] | (seg[1::2] << np.uint64(32)))
        if ef:
            high = np.cumsum(r.unary_many(PACK_BLOCK))
            r.pos = 256
            out.append(b0 + ((high << w) | r.read_many(PACK_BLOCK, w)))
        else:
            out.append(b0 + r.read_many(PACK_BLOCK, w))
    return np.concatenate(out)[:n]


def _block_values(kind, n):
    rng = np.random.default_rng(n)
    if kind == "sorted":
        return _sorted_values(rng, n, 1 << 24)
    if kind == "unsorted":
        return rng.integers(0, 2**31 - 1, size=n)
    return np.full(n, 123456, dtype=np.int64)    # width-0 blocks


@pytest.mark.parametrize("kind", ["sorted", "unsorted", "equal"])
@pytest.mark.parametrize("n", [0, 1, 127, 128, 129, 10_000])
@pytest.mark.parametrize("codec", ["ef", "bitpack"])
def test_pack_matches_the_per_block_coder(codec, n, kind):
    v = _block_values(kind, n)
    pk = pack_postings(v, codec)
    want = _ref_pack(v, codec)
    for name, ref in zip(("words", "base", "meta", "wordoff"), want):
        got = np.asarray(getattr(pk, name))
        assert got.dtype == np.int32, name
        np.testing.assert_array_equal(got.view(ref.dtype) if name == "words"
                                      else got, ref, err_msg=name)
    np.testing.assert_array_equal(unpack_postings(pk), v.astype(np.int32))
    np.testing.assert_array_equal(
        _ref_unpack(*want, n).astype(np.int32), v.astype(np.int32))


def test_pack_rejects_unknown_codec():
    with pytest.raises(ValueError):
        pack_postings(np.arange(10), "snappy")


def test_ef_codec_compresses_sorted_runs():
    # clustered sorted postings: EF payloads should beat plain bitpack
    rng = np.random.default_rng(11)
    v = np.sort(rng.choice(1 << 22, size=20_000, replace=False))
    bpi_ef = pack_postings(v, "ef").bits_per_int()
    bpi_bp = pack_postings(v, "bitpack").bits_per_int()
    assert bpi_ef < bpi_bp
    assert bpi_ef < 32.0 / 2     # >= 2x vs raw int32 on this distribution


def _lookup_all(pk: PackedPostings, ptrs):
    fn = jax.jit(lambda p: packed_lookup(
        pk.words, pk.base, pk.meta, pk.wordoff, p,
        n_post=pk.n_post, ef=pk.has_ef))
    return np.asarray(fn(jnp.asarray(ptrs, jnp.int32)))


@pytest.mark.parametrize("codec", ["ef", "bitpack"])
def test_packed_lookup_parity_jit(codec):
    rng = np.random.default_rng(13)
    v = _csr_like(rng, 60, 80, 1 << 20)
    pk = pack_postings(v, codec)
    ptrs = np.arange(len(v), dtype=np.int32)
    assert np.array_equal(_lookup_all(pk, ptrs), v.astype(np.int32))
    # out-of-bounds pointers clamp exactly like XLA's gather clamp
    oob = np.array([-5, -1, len(v), len(v) + 7, 2**30], dtype=np.int32)
    want = v.astype(np.int32)[np.clip(oob, 0, len(v) - 1)]
    assert np.array_equal(_lookup_all(pk, oob), want)


def test_packed_lookup_single_element():
    pk = pack_postings(np.array([77], dtype=np.int64), "ef")
    assert np.array_equal(_lookup_all(pk, np.array([-1, 0, 1, 100])),
                          np.full(4, 77, dtype=np.int32))


# ---------------------------------------------------------------- hypothesis
@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(0, 2**31 - 1), min_size=0, max_size=600))
def test_hyp_ef_roundtrip(vals):
    v = np.sort(np.asarray(vals, dtype=np.int64))
    assert np.array_equal(ef_decode(ef_encode(v)), v)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(0, 2**31 - 1), min_size=0, max_size=300))
def test_hyp_vbyte_roundtrip(vals):
    v = np.sort(np.asarray(vals, dtype=np.int64))
    assert np.array_equal(vbyte_decode(vbyte_encode(v), len(v)), v)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=520),
       st.sampled_from(["ef", "bitpack"]),
       st.booleans())
def test_hyp_pack_roundtrip_and_lookup(vals, codec, sort):
    v = np.asarray(vals, dtype=np.int64)
    if sort:
        v = np.sort(v)
    pk = pack_postings(v, codec)
    assert np.array_equal(unpack_postings(pk), v.astype(np.int32))
    ptrs = np.arange(len(v), dtype=np.int32)
    assert np.array_equal(_lookup_all(pk, ptrs), v.astype(np.int32))
