"""Live ingest: the append path, the Generation API, and the
zero-pause RCU swap.

Pins the PR-10 contracts:

  * ``runtime.generation`` is the single authority: placement and
    content are minted by one clock, nowhere else.
  * CSR postings appends are bit-for-bit a from-scratch rebuild.
  * Frozen-model batch inference is bit-for-bit the per-doc path.
  * ``refresh_appended`` leaves untouched rows byte-identical and
    makes touched rows match a full rebuild's ops.
  * The qcache fences on *content* changes (the ``attach_corpus``
    regression), not just placement.
  * A query racing an ingest swap returns bit-for-bit either the
    pre-append or the post-append answer — never a torn one.
"""
import threading

import numpy as np
import pytest

from repro.core.index import refresh_appended
from repro.core.queries import parse_boolean
from repro.core.queries.batch import BatchQuery, QueryBatch
from repro.data.store import (
    DocShard,
    Document,
    ShardedCorpus,
    build_postings,
    merge_postings,
    shard_postings,
)
from repro.launch.serve_stack import ServeConfig, build_serving_stack
from repro.runtime.generation import Generation, GenerationClock
from repro.runtime.placement import HostGroupExecutor, PlacementMap
from repro.runtime.qcache import SemanticQueryCache


def _rand_docs(rng, n, vocab, mean_len=30):
    return [rng.integers(0, vocab, size=int(rng.integers(5, mean_len * 2)))
            .astype(np.int32) for _ in range(n)]


# ---------------------------------------------------------------------------
# the Generation API
# ---------------------------------------------------------------------------
def test_generation_clock_axes_are_independent():
    clock = GenerationClock()
    assert clock.current() == Generation(0, 0)
    assert clock.bump_placement() == Generation(1, 0)
    assert clock.bump_content() == Generation(1, 1)
    assert clock.bump_content() == Generation(1, 2)
    assert clock.current() == Generation(placement=1, content=2)
    assert clock.current().record() == dict(placement=1, content=2)


def test_generation_is_hashable_value_type():
    a, b = Generation(2, 3), Generation(2, 3)
    assert a == b and hash(a) == hash(b)
    assert Generation(2, 4) != a and Generation(3, 3) != a
    # never equal to a bare int: a value stamped with an int can't
    # validate against a Generation probe (or vice versa)
    assert Generation(1, 0) != 1


def test_clock_mints_under_concurrency():
    clock = GenerationClock()

    def spin(bump, n=200):
        for _ in range(n):
            bump()

    threads = [threading.Thread(target=spin, args=(clock.bump_placement,)),
               threading.Thread(target=spin, args=(clock.bump_content,))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert clock.current() == Generation(200, 200)


def test_set_placement_bumps_the_clock_once_per_swap():
    """Every ``set_placement`` mints exactly one placement generation
    on the host group's clock and leaves the content axis alone."""
    pm = PlacementMap.blocked(8, 2)
    ex = HostGroupExecutor(pm, workers_per_host=1)
    try:
        assert ex.clock.current() == Generation(0, 0)
        ex.set_placement(PlacementMap.blocked(8, 2))
        assert ex.clock.current() == Generation(1, 0)
        ex.set_placement(PlacementMap.blocked(8, 2))
        assert ex.clock.current() == Generation(2, 0)
    finally:
        ex.close()


def test_placement_extend():
    pm = PlacementMap.blocked(6, 2, n_replicas=1)
    grown = pm.extend(9)
    # old shards keep their primaries; new ones exist and are valid
    assert np.array_equal(grown.primary[:6], pm.primary[:6])
    assert grown.n_shards == 9 and grown.n_hosts == pm.n_hosts
    assert all(0 <= int(h) < pm.n_hosts for h in grown.primary)
    assert pm.extend(6) is pm
    with pytest.raises(ValueError):
        pm.extend(3)


# ---------------------------------------------------------------------------
# the store append path
# ---------------------------------------------------------------------------
def test_append_unbounded_grows_open_shard_bit_for_bit():
    rng = np.random.default_rng(0)
    base = _rand_docs(rng, 40, vocab=64)
    docs = [Document(i, t) for i, t in enumerate(base)]
    corpus = ShardedCorpus.from_documents(docs, 64, shard_tokens=512)
    # force postings to exist pre-append so the delta-merge path runs
    for s in corpus.shards:
        shard_postings(s)
    extra = _rand_docs(rng, 15, vocab=64)
    grown, new_ids, affected = corpus.append_documents(extra)

    assert grown.n_shards == corpus.n_shards
    assert affected == [corpus.n_shards - 1]
    assert np.array_equal(new_ids, np.arange(40, 55))
    # untouched shards are shared by reference (copy-on-write)
    for sid in range(corpus.n_shards - 1):
        assert grown.shards[sid] is corpus.shards[sid]
    # merged postings == from-scratch rebuild, bit for bit
    open_shard = grown.shards[-1]
    merged = open_shard._postings
    assert merged is not None, "delta merge should reuse the built CSR"
    rebuilt = build_postings(DocShard.from_documents(
        open_shard.shard_id, list(open_shard.iter_documents())))
    assert np.array_equal(merged.indptr, rebuilt.indptr)
    assert np.array_equal(merged.doc_idx, rebuilt.doc_idx)
    assert np.array_equal(merged.tf, rebuilt.tf)


def test_append_budgeted_spills_like_from_documents():
    rng = np.random.default_rng(1)
    base = _rand_docs(rng, 30, vocab=32)
    extra = _rand_docs(rng, 30, vocab=32)
    docs = [Document(i, t) for i, t in enumerate(base)]
    corpus = ShardedCorpus.from_documents(docs, 32, shard_tokens=256)
    grown, new_ids, affected = corpus.append_documents(
        extra, shard_tokens=256)
    # identical to building the whole corpus at once
    all_docs = [Document(i, t) for i, t in enumerate(base + extra)]
    oracle = ShardedCorpus.from_documents(all_docs, 32, shard_tokens=256)
    assert grown.n_shards == oracle.n_shards
    assert grown.n_docs == oracle.n_docs == 60
    for a, b in zip(grown.shards, oracle.shards):
        assert np.array_equal(a.doc_ids, b.doc_ids)
        assert np.array_equal(a.tokens, b.tokens)
        assert np.array_equal(a.offsets, b.offsets)
    assert affected  # the open shard changed, plus any spilled ones
    assert grown.n_shards > corpus.n_shards  # this budget does spill


def test_append_empty_is_identity():
    rng = np.random.default_rng(2)
    docs = [Document(i, t) for i, t in enumerate(_rand_docs(rng, 5, 16))]
    corpus = ShardedCorpus.from_documents(docs, 16, shard_tokens=128)
    same, ids, affected = corpus.append_documents([])
    assert same is corpus and len(ids) == 0 and affected == []


def test_merge_postings_handles_vocab_growth():
    """A delta whose max token exceeds the old shard's local vocab
    must widen the merged CSR, not truncate it."""
    old_docs = [Document(0, np.asarray([1, 1, 2], np.int32))]
    new_docs = [Document(1, np.asarray([5, 2], np.int32))]
    old = build_postings(DocShard.from_documents(0, old_docs))
    delta = build_postings(DocShard.from_documents(0, new_docs))
    merged = merge_postings(old, 1, delta)
    rebuilt = build_postings(DocShard.from_documents(0, old_docs + new_docs))
    assert np.array_equal(merged.indptr, rebuilt.indptr)
    assert np.array_equal(merged.doc_idx, rebuilt.doc_idx)
    assert np.array_equal(merged.tf, rebuilt.tf)


# ---------------------------------------------------------------------------
# frozen-model inference + incremental index refresh
# ---------------------------------------------------------------------------
def test_infer_doc_vectors_matches_per_doc_path(pv_model):
    from repro.core import pv_dbow as pv
    model, cfg = pv_model
    rng = np.random.default_rng(3)
    docs = _rand_docs(rng, 4, vocab=model.word_vecs.shape[0])
    batch = pv.infer_doc_vectors(model, docs, cfg, steps=6)
    assert batch.shape == (4, cfg.dim) and batch.dtype == np.float32
    for j, d in enumerate(docs):
        one = np.asarray(pv.infer_doc_vector(model, d, cfg, steps=6),
                         np.float32)
        assert np.array_equal(batch[j], one)
    empty = pv.infer_doc_vectors(model, [], cfg, steps=6)
    assert empty.shape == (0, cfg.dim)


def test_refresh_appended_incremental_vs_rebuild(small_corpus, pv_model,
                                                 built_index):
    model, pcfg = pv_model
    rng = np.random.default_rng(4)
    extra = _rand_docs(rng, 12, vocab=small_corpus.vocab_size)
    grown, new_ids, affected = small_corpus.append_documents(extra)
    new = refresh_appended(built_index, grown, model, pcfg, extra,
                           affected, infer_steps=5)
    # untouched shard rows byte-identical; old doc rows byte-identical
    untouched = [s for s in range(built_index.shard_vecs.shape[0])
                 if s not in set(affected)]
    assert np.array_equal(new.shard_vecs[untouched],
                          built_index.shard_vecs[untouched])
    assert np.array_equal(new.shard_sig[untouched],
                          built_index.shard_sig[untouched])
    assert np.array_equal(new.doc_vecs[:built_index.n_docs],
                          built_index.doc_vecs)
    # touched rows are the exact build op over the new membership
    for sid in affected:
        want = new.doc_vecs[grown.shards[sid].doc_ids].mean(axis=0)
        assert np.array_equal(new.shard_vecs[sid],
                              want.astype(np.float32))
    # exact integer stats deltas
    df = built_index.doc_freq.copy()
    for t in extra:
        df[np.unique(np.asarray(t, np.int64))] += 1
    assert np.array_equal(new.doc_freq, df)
    assert new.n_docs == grown.n_docs
    assert new.avg_doc_len == pytest.approx(
        grown.n_tokens / grown.n_docs)
    # generation continuity: same clock object, caller mints the bump
    assert new.clock is built_index.clock
    # and the old index object is untouched
    assert built_index.n_docs == small_corpus.n_docs


def test_refresh_appended_requires_doc_vectors(small_corpus, pv_model,
                                               built_index):
    import dataclasses as dc
    model, pcfg = pv_model
    stripped = dc.replace(built_index, doc_vecs=None, doc_sig=None)
    extra = [np.asarray([1, 2, 3], np.int32)]
    grown, _, affected = small_corpus.append_documents(extra)
    with pytest.raises(ValueError, match="keep_doc_vectors"):
        refresh_appended(stripped, grown, model, pcfg, extra, affected)
    with pytest.raises(ValueError, match="line up"):
        refresh_appended(built_index, grown, model, pcfg,
                         extra + extra, affected)


# ---------------------------------------------------------------------------
# the content-fence regression (the PR-10 bugfix)
# ---------------------------------------------------------------------------
def test_qcache_fences_on_content_change(small_corpus, built_index):
    """``attach_corpus`` changes what answers mean without touching
    placement — before the content axis existed, the cache kept
    serving estimates computed over the old corpus.  Now the engine's
    composite generation fences them."""
    index = built_index.use_clock(GenerationClock())
    cache = SemanticQueryCache()
    engine = QueryBatch(small_corpus, index, cache=cache)
    q = BatchQuery.count((3, 7))
    r0 = engine.execute([q], 0.5, np.random.default_rng(9))[0]
    r1 = engine.execute([q], 0.5, np.random.default_rng(10))[0]
    assert cache.stats["hits"] == 1
    assert r1.estimate.value == r0.estimate.value  # memoized

    index.attach_corpus(small_corpus)  # content bump, placement same
    engine.execute([q], 0.5, np.random.default_rng(11))
    # the cached entry was dropped as stale, not served
    assert cache.stats["hits"] == 1
    assert cache.stats["stale_epoch"] >= 1


def test_engine_generation_composes_both_axes(small_corpus, built_index):
    index = built_index.use_clock(GenerationClock())
    engine = QueryBatch(small_corpus, index,
                        cache=SemanticQueryCache())
    assert engine._generation() == Generation(0, 0)
    index.clock.bump_content()
    assert engine._generation() == Generation(0, 1)


# ---------------------------------------------------------------------------
# ServeConfig validation + Ingestor lifecycle
# ---------------------------------------------------------------------------
def test_serve_config_ingest_validation(pv_model):
    model, pcfg = pv_model
    with pytest.raises(ValueError, match="ingest_model"):
        ServeConfig(ingest=True)
    with pytest.raises(ValueError, match="ingest=False"):
        ServeConfig(ingest_model=model)
    with pytest.raises(ValueError, match="refresh_docs"):
        ServeConfig(ingest=True, ingest_model=model, ingest_pv_cfg=pcfg,
                    refresh_docs=0)
    with pytest.raises(ValueError, match="refresh_interval_s"):
        ServeConfig(ingest=True, ingest_model=model, ingest_pv_cfg=pcfg,
                    refresh_interval_s=0.0)
    with pytest.raises(ValueError, match="ingest_infer_steps"):
        ServeConfig(ingest=True, ingest_model=model, ingest_pv_cfg=pcfg,
                    ingest_infer_steps=0)
    with pytest.raises(ValueError, match="ingest_yield_s"):
        ServeConfig(ingest=True, ingest_model=model, ingest_pv_cfg=pcfg,
                    ingest_yield_s=-0.001)
    ok = ServeConfig(ingest=True, ingest_model=model, ingest_pv_cfg=pcfg)
    assert ok.ingest and ok.refresh_docs == 64
    # pacing may be disabled outright (throughput-first ingest)
    assert ServeConfig(ingest=True, ingest_model=model,
                       ingest_pv_cfg=pcfg, ingest_yield_s=0.0).ingest


def test_ingestor_step_swaps_and_bumps(small_corpus, pv_model,
                                       built_index):
    model, pcfg = pv_model
    phrase = (small_corpus.vocab_size - 2, small_corpus.vocab_size - 1)
    rng = np.random.default_rng(5)
    new_docs = [np.concatenate([
        np.asarray(phrase, np.int32),
        rng.integers(0, small_corpus.vocab_size - 2, 20).astype(np.int32)])
        for _ in range(10)]
    with build_serving_stack(
            small_corpus, built_index, cache=True, ingest=True,
            ingest_model=model, ingest_pv_cfg=pcfg,
            ingest_infer_steps=4) as stack:
        q = BatchQuery.count(phrase)
        c0 = stack.engine.execute([q], 1.0)[0].estimate.value
        assert stack.generation == Generation(0, 0)
        rec = stack.ingestor.step(new_docs)
        assert rec["appended"] == 10
        assert rec["generation"] == dict(placement=0, content=1)
        assert stack.generation == Generation(0, 1)
        fresh = stack.engine.execute([q], 1.0)[0].estimate
        assert fresh.value == c0 + 10  # freshness: new docs visible
        assert fresh.error_bound == 0.0  # and counted by a census
        assert stack.corpus is stack.engine.corpus
        assert stack.index is stack.engine.index
        ing = stack.ingestor.record()
        assert ing["swaps"] == 1 and ing["docs_appended"] == 10
        # empty step: no swap, no bump
        rec2 = stack.ingestor.step([])
        assert rec2["appended"] == 0
        assert stack.generation == Generation(0, 1)


def test_ingestor_background_source(small_corpus, pv_model, built_index):
    model, pcfg = pv_model
    fed = threading.Event()
    rng = np.random.default_rng(6)

    def source(n):
        if fed.is_set():
            return []
        fed.set()
        return _rand_docs(rng, 5, small_corpus.vocab_size)

    with build_serving_stack(
            small_corpus, built_index, ingest=True,
            ingest_model=model, ingest_pv_cfg=pcfg,
            ingest_source=source, refresh_interval_s=0.01,
            ingest_infer_steps=2) as stack:
        assert stack.ingestor.running
        # the writer has taken the batch once the source has handed it
        # out; close() stops the writer after its in-flight step (the
        # join below outlasts close's own bounded one on a slow host)
        assert fed.wait(timeout=60.0)
        writer = stack.ingestor._thread
        stack.ingestor.close()
        writer.join(timeout=60.0)
        assert not writer.is_alive()
        assert not stack.ingestor.running
        rec = stack.ingestor.record()
        assert rec["docs_appended"] == 5 and rec["errors"] == []
        stack.ingestor.close()  # idempotent
    # stack close after ingestor close is also fine (idempotent path)


# ---------------------------------------------------------------------------
# the RCU property: reads racing a swap are never torn
# ---------------------------------------------------------------------------
def test_read_during_swap_is_pre_or_post_never_torn(small_corpus,
                                                    pv_model,
                                                    built_index):
    """Property test: while ``step`` swaps the world, every concurrent
    *batch* returns bit-for-bit either the pre-append answer or the
    post-append answer (same seed, same rate) — never a mixture of
    the two worlds within one batch, and never an error."""
    model, pcfg = pv_model
    rng = np.random.default_rng(7)
    extra = _rand_docs(rng, 30, small_corpus.vocab_size)
    queries = [BatchQuery.count((3, 7)),
               BatchQuery.ranked((11, 23), k=5),
               BatchQuery.count((5,))]
    seeds = list(range(40, 46))

    def run_one(engine, s):
        res = engine.execute(queries, 0.5, np.random.default_rng(s))
        return tuple(
            (r.estimate.value if r.estimate is not None else None,
             tuple(np.asarray(getattr(r, "doc_ids", []), np.int64)
                   .tolist()), r.lost_shards)
            for r in res)

    def run_all(engine):
        return {s: run_one(engine, s) for s in seeds}

    # reference worlds, computed sequentially on throwaway stacks
    with build_serving_stack(small_corpus, built_index) as ref:
        pre = run_all(ref.engine)
    grown, _, affected = small_corpus.append_documents(extra)
    post_index = refresh_appended(built_index, grown, model, pcfg,
                                  extra, affected, infer_steps=3)
    with build_serving_stack(grown, post_index) as ref:
        post = run_all(ref.engine)

    with build_serving_stack(
            small_corpus, built_index, ingest=True, ingest_model=model,
            ingest_pv_cfg=pcfg, ingest_infer_steps=3) as stack:
        start = threading.Barrier(2)

        def writer():
            start.wait()
            stack.ingestor.step(extra)

        t = threading.Thread(target=writer)
        t.start()
        observed = []
        start.wait()
        for _ in range(20):
            for s in seeds:
                observed.append((s, run_one(stack.engine, s)))
        t.join()
        after = run_all(stack.engine)

    assert after == post  # the swap landed and serves fresh answers
    for s, got in observed:
        assert got == pre[s] or got == post[s], "torn batch during swap"


# ---------------------------------------------------------------------------
# serving around an ingest swap: an idle writer, the cache fence, and
# placement on a host group
# ---------------------------------------------------------------------------
QS = [BatchQuery.count((3, 7)), BatchQuery.ranked((11, 23), k=5),
      BatchQuery.boolean(parse_boolean([3, "or", 5, "and", 9])),
      BatchQuery.count((5,))]


def _same(a, b):
    return repr(a._replace(elapsed_s=0.0)) == repr(b._replace(elapsed_s=0.0))


def _serve(engine, seed0, rounds=3):
    return [r for j in range(rounds)
            for r in engine.execute(QS, 0.5, np.random.default_rng(seed0 + j))]


def test_idle_ingestor_leaves_serving_bit_for_bit(small_corpus, pv_model,
                                                  built_index):
    """Attaching an Ingestor that never steps changes no answer."""
    model, pcfg = pv_model
    with build_serving_stack(small_corpus, built_index, hosts=2) as ref:
        want = _serve(ref.engine, 70)
    with build_serving_stack(
            small_corpus, built_index, hosts=2, ingest=True,
            ingest_model=model, ingest_pv_cfg=pcfg) as stack:
        got = _serve(stack.engine, 70)
        assert stack.generation == Generation(0, 0)
    assert all(_same(g, w) for g, w in zip(got, want))


def test_ingest_swap_fences_every_cached_entry(small_corpus, pv_model,
                                               built_index):
    """A warm cache serves no hit across an ingest step: every entry
    drops as stale, and the re-served answers are a plain engine's on
    the appended world under the same seeds."""
    model, pcfg = pv_model
    extra = _rand_docs(np.random.default_rng(8), 12,
                       small_corpus.vocab_size)
    with build_serving_stack(
            small_corpus, built_index, cache=True, ingest=True,
            ingest_model=model, ingest_pv_cfg=pcfg,
            ingest_infer_steps=3) as stack:
        cache = stack.cache
        _serve(stack.engine, 100, rounds=1)              # populate
        _serve(stack.engine, 140, rounds=1)              # warm re-serve
        assert cache.stats["hits"] == len(QS)            # not vacuous
        stack.ingestor.step(extra)
        got = _serve(stack.engine, 180, rounds=1)
        assert cache.stats["hits"] == len(QS)            # none crossed
        assert cache.stats["stale_epoch"] == len(QS)
        want = _serve(QueryBatch(stack.corpus, stack.index,
                                 executor=stack.executor), 180, rounds=1)
    assert all(_same(g, w) for g, w in zip(got, want))


@pytest.mark.parametrize("shard_tokens,new_shards", [(None, 0), (256, 1)],
                         ids=["grow", "spill"])
def test_ingest_on_host_group_bumps_placement_only_when_shards_spill(
        small_corpus, pv_model, built_index, shard_tokens, new_shards):
    """On a host group the step mints one content generation, and one
    placement generation only when appended docs spill into new shards;
    the appended docs are counted exactly and no shard is lost."""
    model, pcfg = pv_model
    phrase = (small_corpus.vocab_size - 2, small_corpus.vocab_size - 1)
    rng = np.random.default_rng(9)
    new_docs = [np.concatenate([
        np.asarray(phrase, np.int32),
        rng.integers(0, small_corpus.vocab_size - 2, 40).astype(np.int32)])
        for _ in range(12)]
    q = BatchQuery.count(phrase)
    with build_serving_stack(
            small_corpus, built_index, hosts=2, ingest=True,
            ingest_model=model, ingest_pv_cfg=pcfg, ingest_infer_steps=3,
            ingest_shard_tokens=shard_tokens) as stack:
        c0 = stack.engine.execute([q], 1.0)[0].estimate.value
        rec = stack.ingestor.step(new_docs)
        assert (rec["new_shards"] > 0) == bool(new_shards)
        assert stack.generation == Generation(new_shards, 1)
        fresh = stack.engine.execute([q], 1.0)[0]
        assert fresh.estimate.value == c0 + len(new_docs)
        assert fresh.estimate.error_bound == 0.0
        assert all(r.lost_shards == 0 for r in _serve(stack.engine, 200))
        assert stack.executor.stats["lost_shards"] == 0
