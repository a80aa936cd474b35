"""One-launch megascan (kernels/megascan): the block-aligned packed
payload, the streamed vs DMA double-buffered schedules, the bitonic
per-tile top-k epilogue, and the executor megakernel route — pinned
against the pure-jnp oracles, the PR-2 fused segment-sum kernels, and
(bit-for-bit) the per-shard fused path, in interpret mode on CPU per
the harness contract."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lsh as lsh_mod
from repro.data.store import plan_blocked_layout
from repro.kernels.asym import ops as aops
from repro.kernels.blocks import bitonic_sort_desc
from repro.kernels.common import word_major
from repro.kernels.hamming import ops as hops
from repro.kernels.megascan import ops as mops
from repro.kernels.megascan import ref as mref

# ragged shard census the payload must survive: partial last blocks,
# a single-doc shard, an EMPTY shard, and a shard count that is not a
# multiple of the prefetch depth (2)
RAGGED = (13, 8, 1, 0, 27, 64, 5)
QUERIES = [[3, 5, 9], [2], [10, 11], [7, 4, 5, 6]]


def _segments(counts, dim, bits, seed):
    """Per-shard (packed signatures, doc ids) with globally unique ids."""
    rng = np.random.default_rng(seed)
    planes = lsh_mod.hyperplanes(lsh_mod.LSHConfig(bits=bits), dim)
    segs, base = [], 0
    for c in counts:
        x = rng.normal(size=(c, dim)).astype(np.float32)
        sig = np.asarray(lsh_mod.pack_bits(lsh_mod.signature_bits(
            jnp.asarray(x), planes)))
        segs.append((sig, np.arange(base, base + c, dtype=np.int64)))
        base += c
    q = jnp.asarray(rng.normal(size=(5, dim)).astype(np.float32))
    return segs, q, planes


# ----------------------------------------------------------------------
# layout planning + payload packing
# ----------------------------------------------------------------------
def test_plan_blocked_layout_ragged():
    starts, blocks, total = plan_blocked_layout(
        np.array([3, 0, 5, 4]), 4)
    np.testing.assert_array_equal(blocks, [1, 0, 2, 1])
    np.testing.assert_array_equal(starts, [0, 4, 4, 12])
    assert total == 16
    with pytest.raises(ValueError):
        plan_blocked_layout(np.array([1]), 0)
    with pytest.raises(ValueError):
        plan_blocked_layout(np.array([-1]), 4)


def test_build_payload_block_alignment():
    tm = 8
    segs, _, _ = _segments(RAGGED, 16, 64, seed=3)
    pay = mops.build_payload(segs, tm=tm)
    assert pay.n_rows % tm == 0
    assert pay.n_blocks == sum(-(-c // tm) for c in RAGGED)
    slots = np.asarray(pay.slots).ravel()
    # every TM block belongs to exactly one slot (padding rows carry
    # the out-of-range slot_pad, which still "belongs" to the block)
    for j in range(pay.n_blocks):
        blk = slots[j * tm:(j + 1) * tm]
        real = blk[blk != pay.slot_pad]
        assert real.size > 0 and (real == pay.block_slot[j]).all()
    # padding rows are -1 docs; real rows keep their global ids
    np.testing.assert_array_equal(np.asarray(pay.counts), RAGGED)
    assert (pay.doc_idx[slots == pay.slot_pad] == -1).all()
    assert (pay.doc_idx[slots != pay.slot_pad] >= 0).all()
    with pytest.raises(ValueError):
        mops.build_payload(segs, tm=12)     # not a power of two
    with pytest.raises(ValueError):
        mops.build_payload([])


# ----------------------------------------------------------------------
# segment-sum kernels: oracle, fused-kernel, and schedule parity
# ----------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["asym", "hamming"])
def test_megascan_segsum_matches_oracle_and_fused(mode):
    bits, dim, tm = 64, 16, 8
    segs, q, planes = _segments(RAGGED, dim, bits, seed=7)
    pay = mops.build_payload(segs, tm=tm)
    if mode == "hamming":
        q = lsh_mod.pack_bits(lsh_mod.signature_bits(q, planes))
    got = mops.megascan_segment_sums(pay, q, planes, bits, mode=mode,
                                     temperature=4.0)
    want = mref.megascan_segment_sums_ref(pay, q, planes, bits,
                                          mode=mode, temperature=4.0)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    # the PR-2 fused kernels on the real rows only (their own tiling)
    real = np.concatenate([s for s, _ in segs])
    seg_ids = np.concatenate([
        np.full(c, i, np.int32) for i, c in enumerate(RAGGED)])
    if mode == "asym":
        fused = aops.asym_exp_segment_sum(
            q, word_major(real), planes, bits, seg_ids, len(RAGGED),
            temperature=4.0)
    else:
        fused = hops.hamming_segment_similarity(
            q, word_major(real), bits, seg_ids, len(RAGGED),
            temperature=4.0)
    np.testing.assert_allclose(got, np.asarray(fused), rtol=1e-4)


@pytest.mark.parametrize("mode", ["asym", "hamming"])
def test_megascan_double_buffer_is_bitwise(mode):
    """The explicit DMA double-buffered schedule and the BlockSpec grid
    pipeline must be the SAME numbers, not merely close."""
    bits, dim, tm = 64, 16, 8
    segs, q, planes = _segments(RAGGED, dim, bits, seed=11)
    pay = mops.build_payload(segs, tm=tm)
    if mode == "hamming":
        q = lsh_mod.pack_bits(lsh_mod.signature_bits(q, planes))
    streamed = mops.megascan_segment_sums(
        pay, q, planes, bits, mode=mode, double_buffer=False)
    dbuf = mops.megascan_segment_sums(
        pay, q, planes, bits, mode=mode, double_buffer=True)
    np.testing.assert_array_equal(streamed, dbuf)


def test_megascan_group_vs_single_shard_bitwise():
    """The bit-for-bit packing claim: slot s of the group payload's
    output equals a single-shard payload's output for shard s — the
    same guarantee the executor's gather-parity gate rests on."""
    bits, dim, tm = 64, 16, 8
    segs, q, planes = _segments(RAGGED, dim, bits, seed=13)
    group = mops.megascan_segment_sums(
        mops.build_payload(segs, tm=tm), q, planes, bits)
    for s, seg in enumerate(segs):
        single = mops.megascan_segment_sums(
            mops.build_payload([seg], tm=tm), q, planes, bits)
        np.testing.assert_array_equal(group[:, s], single[:, 0])


def test_megascan_empty_payload_and_single_shard_host():
    segs, q, planes = _segments((0, 0), 16, 64, seed=1)
    pay = mops.build_payload(segs, tm=8)
    assert pay.n_rows == 0 and pay.n_blocks == 0
    out = mops.megascan_segment_sums(pay, q, planes, 64)
    np.testing.assert_array_equal(out, np.zeros((5, 2)))
    # a one-shard host is just the degenerate group
    segs, q, planes = _segments((9,), 16, 64, seed=2)
    pay = mops.build_payload(segs, tm=8)
    got = mops.megascan_segment_sums(pay, q, planes, 64)
    want = mref.megascan_segment_sums_ref(pay, q, planes, 64, mode="asym")
    np.testing.assert_allclose(got, want, rtol=1e-4)


# ----------------------------------------------------------------------
# bitonic per-tile top-k
# ----------------------------------------------------------------------
@pytest.mark.parametrize("tm", [8, 128, 256])
def test_bitonic_sort_desc_matches_lax_topk_with_ties(tm):
    rng = np.random.default_rng(tm)
    # quantized values force tie groups; top_k breaks ties by lowest
    # index, the exact order the sort network must reproduce
    vals = jnp.asarray(
        rng.integers(0, tm // 2, (6, tm)).astype(np.float32))
    idx = jax.lax.broadcasted_iota(jnp.int32, (6, tm), 1)
    sv, si = bitonic_sort_desc(vals, idx)
    tv, ti = jax.lax.top_k(vals, tm)
    np.testing.assert_array_equal(np.asarray(sv), np.asarray(tv))
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ti))


def test_bitonic_sort_rejects_non_power_of_two():
    vals = jnp.zeros((2, 12), jnp.float32)
    idx = jnp.zeros((2, 12), jnp.int32)
    with pytest.raises(AssertionError):
        bitonic_sort_desc(vals, idx)


def test_megascan_topk_matches_oracle_and_schedules():
    bits, dim, tm, k = 64, 16, 16, 5
    segs, q, planes = _segments(RAGGED, dim, bits, seed=17)
    pay = mops.build_payload(segs, tm=tm)
    ids, vals = mops.megascan_topk(pay, q, planes, bits, k,
                                   temperature=4.0)
    rids, rvals = mref.megascan_topk_ref(pay, q, planes, bits, k,
                                         temperature=4.0)
    np.testing.assert_array_equal(ids, rids)
    finite = np.isfinite(rvals)
    np.testing.assert_allclose(vals[finite], rvals[finite], rtol=1e-4)
    np.testing.assert_array_equal(np.isfinite(vals), finite)
    # a slot with fewer than k docs pads with -1 / -inf (shard 2 has
    # one doc; shard 3 is empty)
    assert (ids[:, 2, 1:] == -1).all() and (ids[:, 3] == -1).all()
    # both data-movement schedules emit the same candidates
    ids_db, vals_db = mops.megascan_topk(pay, q, planes, bits, k,
                                         temperature=4.0,
                                         double_buffer=True)
    np.testing.assert_array_equal(ids, ids_db)
    np.testing.assert_array_equal(vals, vals_db)


def test_megascan_topk_lane_padding_is_invisible():
    """PR 4's rule carried over: lane-padding K (TPU path) only widens
    the per-tile candidate sets, never changes the answer."""
    bits, dim, tm, k = 64, 16, 256, 7
    segs, q, planes = _segments((300, 40, 9), dim, bits, seed=19)
    pay = mops.build_payload(segs, tm=tm)
    ids_u, vals_u = mops.megascan_topk(pay, q, planes, bits, k,
                                       pad_lanes=False)
    ids_p, vals_p = mops.megascan_topk(pay, q, planes, bits, k,
                                       pad_lanes=True)
    np.testing.assert_array_equal(ids_u, ids_p)
    np.testing.assert_array_equal(vals_u, vals_p)
    # lane-padded k beyond the tile is a hard error, not silence
    with pytest.raises(ValueError):
        mops.megascan_topk(mops.build_payload(segs, tm=8), q, planes,
                           bits, k, pad_lanes=True)


# ----------------------------------------------------------------------
# index payload cache + executor megakernel route
# ----------------------------------------------------------------------
def _doc_index(built_index, corpus):
    return dataclasses.replace(
        built_index, granularity="doc").attach_corpus(corpus)


def test_index_megascan_payload_cached_until_reattach(small_corpus,
                                                      built_index):
    idx = _doc_index(built_index, small_corpus)
    pay = idx.megascan_payload((0, 1, 2), tm=64)
    assert idx.megascan_payload((0, 1, 2), tm=64) is pay
    assert idx.megascan_payload((0, 1, 2), tm=128) is not pay
    assert pay.shard_ids == (0, 1, 2)
    fresh = idx.attach_corpus(small_corpus)
    assert fresh.megascan_payload((0, 1, 2), tm=64) is not pay
    bare = dataclasses.replace(built_index, doc_sig=None, doc_vecs=None)
    with pytest.raises(ValueError):
        bare.megascan_payload((0,))


def _ragged_plans(n_queries, n_shards, rng):
    plans = []
    for i in range(n_queries):
        if i % 3 == 0:
            plans.append([int(rng.integers(n_shards))])
        elif i % 3 == 1:
            sub = rng.choice(n_shards, size=max(2, n_shards // 2),
                             replace=False)
            plans.append(sorted(int(s) for s in sub))
        else:
            plans.append(list(range(n_shards)))
    return plans


def _scan_dicts_equal(got, want):
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        for s in g:
            if isinstance(g[s], dict):
                np.testing.assert_array_equal(g[s]["doc_ids"],
                                              w[s]["doc_ids"])
                np.testing.assert_array_equal(g[s]["values"],
                                              w[s]["values"])
            else:
                assert g[s] == w[s]


@pytest.mark.parametrize("ranked", [False, True])
def test_executor_megakernel_route_bitwise_parity(small_corpus,
                                                  built_index, ranked):
    from repro.kernels.megascan import MegascanSpec
    from repro.runtime.executor import ShardTaskExecutor
    idx = _doc_index(built_index, small_corpus)
    spec = MegascanSpec(idx, idx.query_vectors(QUERIES),
                        ranked_k=6 if ranked else None)
    fns = spec.scan_fns()
    plans = _ragged_plans(len(QUERIES), small_corpus.n_shards,
                          np.random.default_rng(5))
    ex = ShardTaskExecutor(workers=2)
    mega = ex.map_shard_batch(corpus=small_corpus, plan=plans, fns=fns,
                              megakernel=True)
    assert spec.stats["group_launches"] == 1
    assert ex.stats["megascan_jobs"] == 1
    assert "megascan" in ex.last_job
    per = ex.map_shard_batch(corpus=small_corpus, plan=plans, fns=fns,
                             megakernel=False)
    assert spec.stats["shard_launches"] > 0
    _scan_dicts_equal(mega, per)
    ex.close()


def test_executor_megakernel_retry_preserves_parity(small_corpus,
                                                    built_index):
    from repro.kernels.megascan import MegascanSpec
    from repro.runtime.executor import ShardTaskExecutor
    idx = _doc_index(built_index, small_corpus)
    spec = MegascanSpec(idx, idx.query_vectors(QUERIES))
    fns = spec.scan_fns()
    plans = [[0, 1, 2]] * len(QUERIES)
    failed = []

    def flaky(shard_id, attempt):
        if shard_id == 1 and attempt == 1:
            failed.append(shard_id)
            raise RuntimeError("injected")

    ex = ShardTaskExecutor(workers=2, fault_hook=flaky)
    mega = ex.map_shard_batch(corpus=small_corpus, plan=plans, fns=fns,
                              megakernel=True)
    assert failed == [1] and ex.stats["retries"] >= 1
    calm = ShardTaskExecutor(workers=2)
    per = calm.map_shard_batch(corpus=small_corpus, plan=plans, fns=fns,
                               megakernel=False)
    _scan_dicts_equal(mega, per)
    ex.close()
    calm.close()


def test_host_group_runs_one_launch_per_host(small_corpus, built_index):
    from repro.kernels.megascan import MegascanSpec
    from repro.runtime import HostGroupExecutor, PlacementMap
    from repro.runtime.executor import ShardTaskExecutor
    idx = _doc_index(built_index, small_corpus)
    spec = MegascanSpec(idx, idx.query_vectors(QUERIES))
    fns = spec.scan_fns()
    plans = [list(range(small_corpus.n_shards))] * len(QUERIES)
    hg = HostGroupExecutor(
        PlacementMap.blocked(small_corpus.n_shards, 2, n_replicas=1),
        workers_per_host=1)
    got = hg.map_shard_batch(small_corpus, plans, fns)
    for h, hex_ in hg.hosts.items():
        assert hex_.stats["megascan_jobs"] == 1, f"host {h} fell back"
    ex = ShardTaskExecutor(workers=2)
    want = ex.map_shard_batch(corpus=small_corpus, plan=plans, fns=fns,
                              megakernel=False)
    _scan_dicts_equal(got, want)
    hg.close()
    ex.close()


def test_run_shared_scan_megakernel_flag_validation(small_corpus):
    from repro.runtime.executor import ShardTaskExecutor
    ex = ShardTaskExecutor(workers=1)
    with pytest.raises(ValueError):
        ex.map_shard_batch(corpus=small_corpus, plan=[[0]],
                           fns=[lambda shard: 0.0], megakernel=True)
    ex.close()
