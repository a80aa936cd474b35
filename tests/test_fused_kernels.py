"""Fused in-kernel reductions: segment-sum and top-k variants of the
asym / hamming scoring kernels vs the unfused [B, M] + numpy/jnp
reduce references (interpret mode on CPU per the harness contract),
and the ApproxIndex-level fused routes vs their unfused parity paths."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lsh as lsh_mod
from repro.kernels.asym import ops as aops
from repro.kernels.asym import ref as aref
from repro.kernels.common import (SIG_ALIGN, segment_layout, sig_tile,
                                  slot_pad, word_major)
from repro.kernels.hamming import ops as hops
from repro.kernels.hamming import ref as href

QUERIES = [[3, 5, 9], [2], [10, 11], [7, 4, 5, 6]]


def _asym_setup(b, m, dim, bits, seed):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, dim)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(m, dim)).astype(np.float32))
    planes = lsh_mod.hyperplanes(lsh_mod.LSHConfig(bits=bits), dim)
    db = lsh_mod.pack_bits(lsh_mod.signature_bits(x, planes))
    return rng, q, planes, db


# ----------------------------------------------------------------------
# kernel-level: fused segment sum vs unfused matrix + reduce
# ----------------------------------------------------------------------
@pytest.mark.parametrize("b,m,s,dim,bits,temp", [
    (1, 7, 3, 24, 128, 1.0),        # single query, tiny tile
    (5, 613, 37, 48, 128, 8.0),     # ragged M, many segments
    (9, 300, 128, 32, 64, 4.0),     # S == lane width exactly
    (3, 1000, 5, 48, 256, 8.0),     # M over several tiles
])
def test_asym_segment_sum_matches_unfused(b, m, s, dim, bits, temp):
    rng, q, planes, db = _asym_setup(b, m, dim, bits, seed=b * 100 + m)
    seg = np.sort(rng.integers(0, s, m)).astype(np.int32)
    got = aops.asym_exp_segment_sum(q, word_major(db), planes, bits, seg,
                                    s, temperature=temp)
    # unfused reference: full [B, M] matrix, then a numpy segment reduce
    sims = np.asarray(aref.asym_exp_similarity_ref(q, db, planes, bits, temp),
                      np.float64)
    want = np.stack([np.bincount(seg, weights=row, minlength=s)
                     for row in sims])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)


def test_asym_segment_sum_empty_and_unsorted_segments():
    rng, q, planes, db = _asym_setup(4, 200, 32, 128, seed=0)
    wm = word_major(db)
    s = 16
    # all docs in one segment: every other slot must be exactly zero
    seg = np.full(200, 5, np.int32)
    got = np.asarray(aops.asym_exp_segment_sum(q, wm, planes, 128, seg, s))
    assert (got[:, 5] > 0).all()
    mask = np.ones(s, bool)
    mask[5] = False
    np.testing.assert_array_equal(got[:, mask], 0.0)
    # correctness must not depend on segment-sorted doc order
    seg = rng.integers(0, s, 200).astype(np.int32)
    got = np.asarray(aops.asym_exp_segment_sum(q, wm, planes, 128, seg, s))
    sims = np.asarray(aref.asym_exp_similarity_ref(q, db, planes, 128, 1.0),
                      np.float64)
    want = np.stack([np.bincount(seg, weights=row, minlength=s)
                     for row in sims])
    np.testing.assert_allclose(got, want, rtol=1e-4)


def _bincount_ref(q, db, planes, bits, seg, s, temp):
    """The unfused [B, M] matrix, reduced per segment by np.bincount."""
    sims = np.asarray(aref.asym_exp_similarity_ref(q, db, planes, bits,
                                                   temp), np.float64)
    return np.stack([np.bincount(seg, weights=row, minlength=s)
                     for row in sims])


def _runs(slots, counts):
    return np.repeat(np.asarray(slots, np.int32), counts)


# case -> (queries, segments, sorted slot map of the docs, band width)
BAND_CASES = {
    # tile 1 holds slots 125-151: its band crosses the 128-slot line
    "straddles_128": (3, 300, _runs(np.arange(100, 160), 10), 256),
    # 700 docs: tile 2 ends in 68 padding docs, tile 3 is all padding
    "padded_last_tile": (4, 500, np.sort(np.random.default_rng(1).integers(
        300, 360, 700)).astype(np.int32), 128),
    # slots 3, 40, 90, 126 in tile 0 (empty runs between), 700-705 next
    "empty_runs": (2, 1000, _runs([3, 40, 90, 126, 700, 705],
                                  [100, 100, 40, 16, 200, 56]), 128),
    # 19 queries: three row tiles of 8
    "many_queries": (19, 300, np.sort(np.random.default_rng(2).integers(
        0, 300, 1000)).astype(np.int32), 256),
    # 37 segments, one lane's worth of slots
    "under_128_slots": (5, 37, np.sort(np.random.default_rng(3).integers(
        0, 37, 613)).astype(np.int32), 128),
}


@pytest.mark.parametrize("case", sorted(BAND_CASES))
def test_asym_segment_sum_band_matches_unfused(case):
    """The banded kernel contracts each doc tile's one-hot over only its
    window of slots, and sums exactly what the full one-hot did."""
    b, s, seg, width = BAND_CASES[case]
    m = seg.size
    rng, q, planes, db = _asym_setup(b, m, 32, 128, seed=m + s)
    wm = word_major(db)
    lay = segment_layout(seg, s, wm)
    assert lay.width == width < 2048
    want = _bincount_ref(q, db, planes, 128, seg, s, 4.0)
    # laid out once, or per call from the plain map
    for got in (aops.asym_exp_layout_sum(q, wm, planes, 128, lay, s,
                                         temperature=4.0),
                aops.asym_exp_segment_sum(q, wm, planes, 128, seg, s,
                                          temperature=4.0)):
        assert got.shape == (b, s)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)
    ref = aref.asym_exp_segment_sum_ref(q, db, planes, 128, seg, s, 4.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4)


def test_asym_segment_sum_unsorted_layout_spans_every_slot():
    """Docs in no slot order: no tile's band is narrower than the whole
    padded slot axis, so the kernel is the full-width one, and the sums
    still match."""
    rng, q, planes, db = _asym_setup(4, 1024, 32, 128, seed=7)
    seg = rng.integers(0, 1000, 1024).astype(np.int32)
    wm = word_major(db)
    lay = segment_layout(seg, 1000, wm)
    assert lay.width == lay.s_pad == 1024
    assert not np.asarray(lay.starts).any()
    got = aops.asym_exp_layout_sum(q, wm, planes, 128, lay, 1000)
    want = _bincount_ref(q, db, planes, 128, seg, 1000, 1.0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)


_MAP_RNG = np.random.default_rng(5)
# name -> (sorted slot map, segments)
SORTED_MAPS = {
    "uniform": (np.sort(_MAP_RNG.integers(0, 2000, 20_000)), 2000),
    # zero-size runs: empty slots between the occupied ones
    "ragged": (_runs(np.arange(500), _MAP_RNG.integers(0, 200, 500)), 500),
    "one_slot": (np.full(3000, 77), 80),
    "tail_slots": (np.sort(_MAP_RNG.integers(1900, 1950, 4000)), 1950),
}


@pytest.mark.parametrize("name", sorted(SORTED_MAPS))
def test_segment_layout_windows_are_aligned_minimal_covers(name):
    seg, s = SORTED_MAPS[name]
    seg = np.asarray(seg, np.int32)
    wm = word_major(np.zeros((seg.size, 4), np.uint32))
    tm = sig_tile(wm, 256)
    lay = segment_layout(seg, s, wm)
    starts = np.asarray(lay.starts)
    row = np.asarray(lay.seg)[0]
    assert lay.s_pad == max(128, -(-s // 128) * 128)
    assert row.shape == (wm.t.shape[1],)
    np.testing.assert_array_equal(row[:seg.size], seg)
    assert (row[seg.size:] == lay.s_pad).all()
    assert starts.shape == (row.size // tm,)
    assert lay.width % 128 == 0 and 128 <= lay.width <= lay.s_pad
    assert (starts % 128 == 0).all()
    assert (starts >= 0).all() and (starts + lay.width <= lay.s_pad).all()
    tiles = row.reshape(-1, tm)
    reach = 0
    for t, start in zip(tiles, starts):
        real = t[t < lay.s_pad]
        if real.size:
            assert (real >= start).all() and (real < start + lay.width).all()
            reach = max(reach, real.max() - real.min() // 128 * 128 + 1)
    # no narrower lane multiple covers every tile from its aligned start
    assert lay.width == 128 or lay.width - 128 < reach


@pytest.mark.parametrize("n,m,s,bits,temp", [
    (4, 300, 37, 128, 8.0), (1, 64, 3, 64, 1.0), (8, 1000, 121, 256, 4.0),
])
def test_hamming_segment_sum_matches_unfused(n, m, s, bits, temp):
    rng = np.random.default_rng(n * 10 + m)
    w = bits // 32
    q = jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint32))
    db = jnp.asarray(rng.integers(0, 2**32, (m, w), dtype=np.uint32))
    seg = np.sort(rng.integers(0, s, m)).astype(np.int32)
    got = hops.hamming_segment_similarity(q, word_major(db), bits, seg, s,
                                          temperature=temp)
    sims = np.asarray(href.hamming_similarity_ref(q, db, bits),
                      np.float64) ** temp
    want = np.stack([np.bincount(seg, weights=row, minlength=s)
                     for row in sims])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)


# ----------------------------------------------------------------------
# kernel-level: fused top-k vs argsort over the unfused matrix
# ----------------------------------------------------------------------
@pytest.mark.parametrize("b,m,k,dim,bits,temp", [
    (3, 257, 10, 48, 128, 8.0),     # k << tile, ragged M
    (5, 100, 100, 32, 64, 4.0),     # k == M (full sort)
    (2, 700, 300, 24, 128, 1.0),    # k > default tile width
    (2, 50, 7, 24, 64, 2.0),        # tiny M, k far below lane width
])
def test_asym_topk_matches_argsort(b, m, k, dim, bits, temp):
    _, q, planes, db = _asym_setup(b, m, dim, bits, seed=b + m + k)
    idx, vals = aops.asym_exp_topk(q, word_major(db), planes, bits, k,
                                    temperature=temp)
    sims = np.asarray(aref.asym_exp_similarity_ref(q, db, planes, bits, temp))
    order = np.argsort(-sims, axis=1, kind="stable")[:, :k]
    want_vals = np.take_along_axis(sims, order, axis=1)
    # values must agree; indices may differ only where values tie
    np.testing.assert_allclose(np.asarray(vals), want_vals, rtol=1e-4)
    picked = np.take_along_axis(sims, np.asarray(idx), axis=1)
    np.testing.assert_allclose(picked, np.asarray(vals), rtol=1e-5)
    # rows sorted descending
    v = np.asarray(vals)
    assert (np.diff(v, axis=1) <= 1e-6).all()


@pytest.mark.parametrize("b,m,k", [
    (3, 257, 10),                   # kp = 128 within the tile
    (2, 50, 7),                     # kp = 128 exceeds M entirely
])
def test_asym_topk_lane_padding_is_invisible(b, m, k):
    """The TPU lane-pad path (K -> multiple of 128; off by default in
    interpret mode) must return exactly what the unpadded path does —
    padding only widens the per-tile candidate sets."""
    _, q, planes, db = _asym_setup(b, m, 32, 64, seed=b * m + k)
    idx_p, vals_p = aops.asym_exp_topk(q, word_major(db), planes, 64, k,
                                       temperature=4.0, pad_lanes=True)
    idx_u, vals_u = aops.asym_exp_topk(q, word_major(db), planes, 64, k,
                                       temperature=4.0, pad_lanes=False)
    np.testing.assert_allclose(np.asarray(vals_p), np.asarray(vals_u),
                               rtol=1e-6)
    sims = np.asarray(aref.asym_exp_similarity_ref(q, db, planes, 64, 4.0))
    picked = np.take_along_axis(sims, np.asarray(idx_p), axis=1)
    np.testing.assert_allclose(picked, np.asarray(vals_p), rtol=1e-5)


# ----------------------------------------------------------------------
# index-level: fused routes vs unfused parity paths
# ----------------------------------------------------------------------
def _doc_kernel_index(built_index, corpus, lsh_mode):
    return dataclasses.replace(
        built_index, granularity="doc", use_kernel=True,
        lsh_mode=lsh_mode).attach_corpus(corpus)


@pytest.mark.parametrize("lsh_mode", ["asym", "sym"])
def test_index_fused_shard_sims_match_unfused(small_corpus, built_index,
                                              lsh_mode):
    idx = _doc_kernel_index(built_index, small_corpus, lsh_mode)
    fused = idx.shard_similarities_batch(QUERIES, fused=True)
    unfused = idx.shard_similarities_batch(QUERIES, fused=False)
    assert fused.shape == (len(QUERIES), small_corpus.n_shards)
    np.testing.assert_allclose(fused, unfused, rtol=1e-4)


@pytest.mark.parametrize("lsh_mode", ["asym", "sym"])
def test_index_segsum_lanes_reads_its_slot_layout(small_corpus, built_index,
                                                  lsh_mode):
    """The index's one-hot width per doc tile: the asym kernel's band
    over the shard-sorted slot map, every padded slot for the Hamming
    kernel, 0 where planning runs no fused kernel."""
    idx = _doc_kernel_index(built_index, small_corpus, lsh_mode)
    dev = idx._fused_device_arrays()
    _, _, _, seg_sorted, _ = idx._shard_sorted_docs()
    s_pad = slot_pad(small_corpus.n_shards)
    if lsh_mode == "asym":
        lay = dev["layout"]
        np.testing.assert_array_equal(
            np.asarray(lay.seg)[0, :seg_sorted.size], seg_sorted)
        assert lay.s_pad == s_pad and "seg" not in dev
        want = lay.width
    else:                    # the Hamming kernel reads the plain map
        np.testing.assert_array_equal(np.asarray(dev["seg"]), seg_sorted)
        assert "layout" not in dev
        want = s_pad
    assert idx.segsum_lanes == want > 0
    assert built_index.segsum_lanes == 0         # shard-granular
    assert dataclasses.replace(idx, use_kernel=False).segsum_lanes == 0


def test_index_fused_matches_single_query_loop(small_corpus, built_index):
    idx = _doc_kernel_index(built_index, small_corpus, "asym")
    fused = idx.shard_similarities_batch(QUERIES, fused=True)
    singles = np.stack([idx.shard_similarities(q) for q in QUERIES])
    np.testing.assert_allclose(fused, singles, rtol=1e-4)


def test_index_topk_fused_matches_argsort(small_corpus, built_index):
    idx = _doc_kernel_index(built_index, small_corpus, "asym")
    ids_f, vals_f = idx.topk_doc_similarities_batch(QUERIES, k=9, fused=True)
    ids_r, vals_r = idx.topk_doc_similarities_batch(QUERIES, k=9, fused=False)
    assert ids_f.shape == vals_f.shape == (len(QUERIES), 9)
    np.testing.assert_allclose(vals_f, vals_r, rtol=1e-4)
    # fused picks must carry their true similarity values
    sims = idx._exp_sim_batch(idx.query_vectors(QUERIES), idx.doc_sig,
                              idx.doc_vecs, "doc")
    picked = np.take_along_axis(sims, ids_f, axis=1)
    np.testing.assert_allclose(picked, vals_f, rtol=1e-4)


def test_word_major_layout_is_padded_transpose():
    db = np.arange(3 * 700, dtype=np.uint32).reshape(700, 3)
    wm = word_major(db)
    assert wm.n == 700 and wm.t.shape == (3, 2 * SIG_ALIGN)
    np.testing.assert_array_equal(np.asarray(wm.t)[:, :700], db.T)
    np.testing.assert_array_equal(np.asarray(wm.t)[:, 700:], 0)
    assert sig_tile(wm, 4096) == 2 * SIG_ALIGN
    with pytest.raises(ValueError, match="does not divide"):
        sig_tile(wm, 384)


def test_index_uploads_word_major_signatures_once(small_corpus, built_index):
    """The served planning path reads the index's cached word-major
    signatures: no per-batch re-layout of the signature database."""
    idx = _doc_kernel_index(built_index, small_corpus, "asym")
    idx.shard_similarities_batch(QUERIES)
    sig = idx._fused_device_arrays()["sig"]
    n, w = idx.doc_sig.shape
    assert sig.n == n
    assert sig.t.shape == (w, -(-n // SIG_ALIGN) * SIG_ALIGN)
    idx.shard_similarities_batch(QUERIES[:2])
    idx.topk_doc_similarities_batch(QUERIES, k=3)
    doc = idx._kernel_sigs(idx.doc_sig, "doc")
    idx.topk_doc_similarities_batch(QUERIES[:1], k=3)
    assert idx._fused_device_arrays()["sig"] is sig
    assert idx._kernel_sigs(idx.doc_sig, "doc") is doc


def test_index_topk_requires_doc_vectors(built_index):
    idx = dataclasses.replace(built_index, doc_sig=None, doc_vecs=None)
    with pytest.raises(ValueError):
        idx.topk_doc_similarities_batch(QUERIES, k=3)


def test_sum_docs_to_shards_batch_vectorized_matches_bincount(
        small_corpus, built_index):
    """The reduceat rewrite is exactly the per-row bincount it replaced
    (incl. rows of zeros and the B=1 edge)."""
    idx = dataclasses.replace(built_index,
                              granularity="doc").attach_corpus(small_corpus)
    rng = np.random.default_rng(7)
    vals = rng.uniform(0.0, 5.0, (6, small_corpus.n_docs))
    vals[2] = 0.0
    got = idx._sum_docs_to_shards_batch(vals)
    want = np.stack([np.bincount(idx._doc_shard_ids, weights=row,
                                 minlength=small_corpus.n_shards)
                     for row in vals])
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)
    one = idx._sum_docs_to_shards_batch(vals[:1])
    np.testing.assert_allclose(one, want[:1], rtol=1e-10, atol=1e-12)


def test_sum_docs_to_shards_batch_trailing_empty_shards(built_index):
    """Regression: a trailing empty shard (possible after reallocate
    leaves a k-means cluster empty) must not truncate the last
    non-empty shard's sum."""
    idx = dataclasses.replace(built_index)
    idx.shard_vecs = idx.shard_vecs[:4]
    # 2 docs both in shard 0; shards 1..3 empty (incl. the tail)
    idx._doc_shard_ids = np.asarray([0, 0], np.int64)
    got = idx._sum_docs_to_shards_batch(np.asarray([[1.0, 2.0]]))
    np.testing.assert_allclose(got, [[3.0, 0.0, 0.0, 0.0]])
    # empty shard sandwiched between non-empty ones
    idx._shard_sort = None              # drop the cached sort structures
    idx._doc_shard_ids = np.asarray([0, 0, 2, 3], np.int64)
    got = idx._sum_docs_to_shards_batch(
        np.asarray([[1.0, 2.0, 4.0, 8.0], [1.0, 1.0, 1.0, 1.0]]))
    np.testing.assert_allclose(got, [[3.0, 0.0, 4.0, 8.0],
                                     [2.0, 0.0, 1.0, 1.0]])
