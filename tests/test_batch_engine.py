"""Batched query engine: batched-vs-single score parity across every
LSH mode, postings-vs-scan parity, shared-scan executor correctness
(incl. under injected faults), end-to-end QueryBatch equivalence, and
index save/load round-trip fidelity."""
import dataclasses
import threading

import numpy as np
import pytest

from repro.core.index import ApproxIndex
from repro.core.queries import (
    BatchQuery,
    QueryBatch,
    boolean_query,
    parse_boolean,
    phrase_count_query,
    ranked_query,
)
from repro.core.queries.retrieval import (
    _expr_eval_docs,
    _expr_eval_docs_scan,
    bm25_scores_for_shard,
    bm25_scores_for_shard_scan,
)
from repro.data.store import (
    docs_matching_all,
    docs_matching_all_scan,
    shard_postings,
)
from repro.runtime.executor import ShardTaskExecutor

QUERIES = [[3, 5, 9], [2], [10, 11], [7, 4, 5, 6]]


# ----------------------------------------------------------------------
# batched vs single-query scoring parity (all index modes)
# ----------------------------------------------------------------------
def _variants(index, corpus):
    yield "asym", index
    yield "asym+kernel", dataclasses.replace(index, use_kernel=True)
    yield "sym", dataclasses.replace(index, lsh_mode="sym")
    yield "sym+kernel", dataclasses.replace(index, lsh_mode="sym",
                                            use_kernel=True)
    yield "real", dataclasses.replace(index, use_lsh=False)
    yield "doc-granular", dataclasses.replace(
        index, granularity="doc").attach_corpus(corpus)


def test_batched_scores_match_single(small_corpus, built_index):
    for name, idx in _variants(built_index, small_corpus):
        batch = idx.shard_similarities_batch(QUERIES)
        singles = np.stack([idx.shard_similarities(q) for q in QUERIES])
        assert batch.shape == (len(QUERIES), small_corpus.n_shards)
        np.testing.assert_allclose(batch, singles, rtol=1e-5,
                                   err_msg=f"variant {name}")


def test_batched_word_scores_match_single(built_index):
    words = [3, 7, 9, 1500]
    batch = built_index.word_shard_similarities_batch(words)
    singles = np.stack([built_index.word_shard_similarity(w) for w in words])
    np.testing.assert_allclose(batch, singles, rtol=1e-5)


def test_signs_cache_keyed_by_role(built_index):
    built_index.shard_similarities([1, 2])
    built_index.word_shard_similarity(3)
    cache = getattr(built_index, "_signs")
    assert set(cache) <= {"shard", "doc", "word"}
    assert cache["shard"].shape == (built_index.shard_sig.shape[0],
                                    built_index.bits)


# ----------------------------------------------------------------------
# postings vs flat-scan parity
# ----------------------------------------------------------------------
def test_postings_bm25_matches_scan(small_corpus, built_index):
    rng = np.random.default_rng(0)
    df = built_index.doc_freq
    # out-of-vocab probes need a df entry too
    df_ext = np.concatenate([df, np.ones(64, np.int64)])
    for shard in small_corpus.shards[:6]:
        words = rng.integers(0, small_corpus.vocab_size + 60, 5).tolist()
        a = bm25_scores_for_shard(shard, words, df_ext, built_index.n_docs,
                                  built_index.avg_doc_len)
        b = bm25_scores_for_shard_scan(shard, words, df_ext,
                                       built_index.n_docs,
                                       built_index.avg_doc_len)
        np.testing.assert_array_equal(a, b)


def test_postings_boolean_matches_scan(small_corpus):
    rng = np.random.default_rng(1)
    for shard in small_corpus.shards[:6]:
        w = rng.integers(0, small_corpus.vocab_size + 60, 3)
        expr = parse_boolean([int(w[0]), "or", int(w[1]), "and", int(w[2])])
        np.testing.assert_array_equal(_expr_eval_docs(expr, shard),
                                      _expr_eval_docs_scan(expr, shard))
        np.testing.assert_array_equal(
            docs_matching_all(shard, w[:2].tolist()),
            docs_matching_all_scan(shard, w[:2].tolist()))


def test_postings_cached_and_counts(small_corpus):
    shard = small_corpus.shards[0]
    post = shard_postings(shard)
    assert post is shard_postings(shard)  # lazily built once, reused
    for w in (0, 5, 10**6):
        assert post.word_count(w) == int(np.count_nonzero(shard.tokens == w))


# ----------------------------------------------------------------------
# shared-scan executor
# ----------------------------------------------------------------------
class _FakeShard:
    def __init__(self, i):
        self.shard_id = i


class _FakeCorpus:
    def __init__(self, n):
        self.shards = [_FakeShard(i) for i in range(n)]


def test_map_shard_batch_matches_per_query_map_shards():
    corpus = _FakeCorpus(12)
    plan = [[0, 3, 5], [3, 5, 7, 9], [1], []]
    fns = [lambda s, k=k: (k, s.shard_id) for k in range(len(plan))]
    ex = ShardTaskExecutor(workers=3)
    got = ex.map_shard_batch(corpus, plan, fns)
    for qi, (ids, fn) in enumerate(zip(plan, fns)):
        want = ShardTaskExecutor(workers=3).map_shards(corpus, ids, fn)
        assert got[qi] == want


def test_map_shard_batch_visits_union_once():
    corpus = _FakeCorpus(10)
    visits = []
    lock = threading.Lock()

    def track(qi):
        def fn(shard):
            with lock:
                visits.append((qi, shard.shard_id))
            return shard.shard_id
        return fn

    plan = [[0, 1, 2, 3], [2, 3, 4, 5], [3, 4, 5, 6]]
    ex = ShardTaskExecutor(workers=1)  # no speculation -> exact visit count
    ex.map_shard_batch(corpus, plan, [track(q) for q in range(3)])
    # every (query, shard) pair evaluated exactly once; the underlying
    # shard visit count equals the union, not the sum of plan sizes
    assert sorted(visits) == sorted(
        (qi, s) for qi, ids in enumerate(plan) for s in ids)


def test_map_shard_batch_retries_faults():
    corpus = _FakeCorpus(8)
    fails = {"n": 0}

    def hook(sid, attempt):
        if sid == 2 and attempt == 1:
            fails["n"] += 1
            raise RuntimeError("injected")

    ex = ShardTaskExecutor(workers=2, max_retries=2, fault_hook=hook)
    plan = [[0, 2, 4], [2, 6]]
    got = ex.map_shard_batch(corpus, plan,
                             [lambda s: s.shard_id * 10,
                              lambda s: s.shard_id + 1])
    assert fails["n"] == 1 and ex.stats["retries"] == 1
    assert got[0] == {0: 0, 2: 20, 4: 40}
    assert got[1] == {2: 3, 6: 7}


def test_map_shard_batch_length_mismatch():
    with pytest.raises(ValueError):
        ShardTaskExecutor().map_shard_batch(_FakeCorpus(2), [[0]], [])


# ----------------------------------------------------------------------
# QueryBatch end-to-end
# ----------------------------------------------------------------------
def _mixed_queries():
    return [BatchQuery.count([5]),
            BatchQuery.ranked([3, 8, 11], k=5),
            BatchQuery.boolean(parse_boolean([4, "or", 9, "and", 12])),
            BatchQuery.count([7, 2]),
            BatchQuery.ranked([1, 2], k=8)]


@pytest.mark.parametrize("use_executor", [False, True])
def test_query_batch_matches_single_query_loop(small_corpus, built_index,
                                               use_executor):
    ex = ShardTaskExecutor(workers=3) if use_executor else None
    queries = _mixed_queries()
    got = QueryBatch(small_corpus, built_index, executor=ex).execute(
        queries, 0.3, rng=np.random.default_rng(42))
    rng = np.random.default_rng(42)
    want = [phrase_count_query(small_corpus, built_index, [5], 0.3, rng=rng),
            ranked_query(small_corpus, built_index, [3, 8, 11], 0.3, k=5,
                         rng=rng),
            boolean_query(small_corpus, built_index,
                          parse_boolean([4, "or", 9, "and", 12]), 0.3,
                          rng=rng),
            phrase_count_query(small_corpus, built_index, [7, 2], 0.3,
                               rng=rng),
            ranked_query(small_corpus, built_index, [1, 2], 0.3, k=8,
                         rng=rng)]
    np.testing.assert_allclose(got[0].estimate.value, want[0].estimate.value,
                               rtol=1e-6)
    np.testing.assert_allclose(got[3].estimate.value, want[3].estimate.value,
                               rtol=1e-6)
    np.testing.assert_array_equal(got[1].doc_ids, want[1].doc_ids)
    np.testing.assert_allclose(got[1].scores, want[1].scores, rtol=1e-12)
    np.testing.assert_array_equal(got[2].doc_ids, want[2].doc_ids)
    np.testing.assert_array_equal(got[4].doc_ids, want[4].doc_ids)
    for g, w in zip(got, want):
        assert g.shards_read == w.shards_read


def test_query_batch_precise_and_srcs(small_corpus, built_index):
    queries = _mixed_queries()
    precise = QueryBatch(small_corpus, built_index).execute(queries, 1.0)
    assert precise[0].estimate.error_bound == 0.0
    assert precise[0].estimate.value == small_corpus.count_phrase([5])
    assert precise[0].shards_read == small_corpus.n_shards
    # srcs needs no index at all
    srcs = QueryBatch(small_corpus, None, method="srcs").execute(
        queries, 0.3, rng=np.random.default_rng(3))
    assert len(srcs) == len(queries)
    with pytest.raises(ValueError):
        QueryBatch(small_corpus, None)          # emapprox requires index
    with pytest.raises(ValueError):
        QueryBatch(small_corpus, built_index, method="nope")


_KIND_BATCHES = {
    "count": [BatchQuery.count([5]), BatchQuery.count([7, 2]),
              BatchQuery.count([3, 8, 11])],
    "boolean": [BatchQuery.boolean(parse_boolean([4, "or", 9, "and", 12])),
                BatchQuery.boolean(parse_boolean([3, "and", 5]))],
    "ranked": [BatchQuery.ranked([3, 8, 11], k=5),
               BatchQuery.ranked([1, 2], k=8)],
}


@pytest.mark.parametrize("kind", list(_KIND_BATCHES))
def test_query_batch_sweep_matches_pool(small_corpus, built_index, kind):
    """The same batch through an executor that sweeps on the calling
    thread and through one pinned to the pool: identical answers and
    executed plans, and the report says which ran."""
    runs = {}
    # a floor of 1 s keeps every task of the small corpus in the sweep
    for mode, floor in (("sweep", 1.0), ("pooled", 0.0)):
        with ShardTaskExecutor(workers=3, gil_floor_s=floor) as ex:
            eng = QueryBatch(small_corpus, built_index, executor=ex)
            got = eng.execute(_KIND_BATCHES[kind], 0.3,
                              rng=np.random.default_rng(42))
            runs[mode] = (got, eng.last_report)
    (sweep, sweep_rep), (pooled, pooled_rep) = runs["sweep"], runs["pooled"]
    for a, b in zip(sweep, pooled):
        np.testing.assert_equal(a._replace(elapsed_s=0.0)._asdict(),
                                b._replace(elapsed_s=0.0)._asdict())
    assert len(sweep_rep.plan) == len(pooled_rep.plan)
    for a, b in zip(sweep_rep.plan, pooled_rep.plan):
        np.testing.assert_array_equal(a, b)
    assert sweep_rep.counters["scan_sweep"] == 1
    assert pooled_rep.counters["scan_sweep"] == 0


def test_query_batch_under_faults(small_corpus, built_index):
    fails = {"n": 0}

    def hook(sid, attempt):
        if sid in (0, 1) and attempt == 1:
            fails["n"] += 1
            raise RuntimeError("injected")

    ex = ShardTaskExecutor(workers=3, max_retries=2, fault_hook=hook)
    got = QueryBatch(small_corpus, built_index, executor=ex).execute(
        _mixed_queries(), 1.0)
    assert fails["n"] == 2 and ex.stats["retries"] == 2
    assert got[0].estimate.value == small_corpus.count_phrase([5])


# ----------------------------------------------------------------------
# save/load round-trip (granularity / use_kernel / doc->shard map)
# ----------------------------------------------------------------------
def test_save_load_preserves_execution_config(tmp_path, small_corpus,
                                              built_index):
    idx = dataclasses.replace(built_index, granularity="doc",
                              use_kernel=True).attach_corpus(small_corpus)
    p = str(tmp_path / "index.npz")
    idx.save(p)
    loaded = ApproxIndex.load(p)
    assert loaded.granularity == "doc"
    assert loaded.use_kernel is True
    assert loaded.lsh_mode == idx.lsh_mode
    np.testing.assert_array_equal(loaded._doc_shard_ids, idx._doc_shard_ids)
    # a persisted doc-granular index must score doc-granular after load
    np.testing.assert_allclose(loaded.shard_similarities([3, 5]),
                               idx.shard_similarities([3, 5]), rtol=1e-6)


# ----------------------------------------------------------------------
# error-budgeted execution (runtime.budget.RatePlanner integration)
# ----------------------------------------------------------------------
def test_planner_engine_parity_for_unbudgeted_queries(small_corpus,
                                                      built_index):
    """A planner on the engine must be bit-for-bit invisible to queries
    that carry no budget — including the precise rate-1.0 fast path and
    with CI construction on (the bootstrap never touches the sampling
    rng)."""
    from repro.runtime import RatePlanner
    queries = _mixed_queries()
    for rate in (0.3, 1.0):
        plain = QueryBatch(small_corpus, built_index).execute(
            queries, rate, rng=np.random.default_rng(21))
        planned = QueryBatch(
            small_corpus, built_index,
            planner=RatePlanner(small_corpus.n_shards),
            ci=True).execute(queries, rate, rng=np.random.default_rng(21))
        for q, a, b in zip(queries, plain, planned):
            if q.kind == "count":
                assert b.estimate.value == a.estimate.value
                assert b.estimate.error_bound == a.estimate.error_bound
            else:
                np.testing.assert_array_equal(b.doc_ids, a.doc_ids)
                if hasattr(a, "scores"):
                    np.testing.assert_array_equal(b.scores, a.scores)
            assert b.shards_read == a.shards_read


def test_budgeted_queries_plan_their_own_rates(small_corpus, built_index):
    from repro.runtime import QueryBudget, RatePlanner
    planner = RatePlanner(small_corpus.n_shards)
    engine = QueryBatch(small_corpus, built_index, planner=planner,
                        ci=True)
    assert engine.accepts_pressure
    budget = QueryBudget(max_rel_error=0.6, floor_rate=0.25)
    queries = [dataclasses.replace(q, budget=budget)
               for q in _mixed_queries()]
    res = engine.execute(queries, 0.3, rng=np.random.default_rng(5))
    audit = engine.last_report.budget
    assert audit is not None
    assert audit["budgeted"] == len(queries)
    assert audit["pressure"] == 0.0 and audit["degraded"] == 0
    n = small_corpus.n_shards
    for q, r, planned in zip(queries, res, audit["planned_rates"]):
        assert 0.25 <= planned <= 1.0
        n_req = int(np.ceil(planned * n))
        if q.kind == "count":
            # with-replacement draws match the plan; *distinct* shards
            # physically read may be fewer (duplicates dedup in I/O)
            assert r.estimate.n == n_req
            assert r.shards_read <= n_req
        else:
            # retrieval samples distinct shards: achieved rate is the
            # ceil-quantized planned rate exactly
            assert r.shards_read == min(n, n_req)
        assert r.estimate is not None          # every kind carries a CI
    assert len(audit["realized_rel_error"]) == len(queries)
    # the loop closed: realized errors fed the per-kind curves
    assert planner.curve("count").count >= 1


def test_budget_pressure_degrades_to_floor(small_corpus, built_index):
    """pressure=1.0 squeezes every budgeted query to its floor and the
    audit lands on the executor's last_job (the balance-audit
    pattern)."""
    from repro.runtime import QueryBudget, RatePlanner
    budget = QueryBudget(max_rel_error=0.5, floor_rate=0.25)
    queries = [dataclasses.replace(q, budget=budget)
               for q in _mixed_queries()]
    ex = ShardTaskExecutor(workers=2)
    engine = QueryBatch(small_corpus, built_index, executor=ex,
                        planner=RatePlanner(small_corpus.n_shards),
                        ci=True)
    res = engine.execute(queries, 0.3, rng=np.random.default_rng(6),
                         pressure=1.0)
    audit = engine.last_report.budget
    assert audit["pressure"] == 1.0
    assert audit["at_floor"] == len(queries)
    assert all(r == pytest.approx(0.25) for r in audit["planned_rates"])
    n = small_corpus.n_shards
    for r in res:
        assert r.shards_read <= int(np.ceil(0.25 * n))
    assert ex.last_job["budget"] == audit
    # a degraded count still reports an honest interval: possibly
    # infinite (collapsed sample), never NaN
    for q, r in zip(queries, res):
        if q.kind == "count":
            assert not np.isnan(r.estimate.error_bound)
    ex.close()


def _budgeted_mixed_workload(corpus, n=48, seed=0):
    """``n`` queries in thirds (count of one word, ``a OR b AND c``,
    ranked top-10 over three words) over mid-frequency words, each
    carrying a budget with floor rate 0.1: counts a relative-error
    budget, Booleans a looser one, ranked a latency budget with an
    error cap."""
    from repro.runtime import QueryBudget
    counts = np.bincount(np.concatenate([s.tokens for s in corpus.shards]),
                         minlength=corpus.vocab_size)
    cand = np.nonzero((counts > 50) & (counts < 1200))[0]
    words = np.random.default_rng(seed).choice(
        cand, min(3 * n, len(cand)), replace=False).astype(int)
    queries = []
    for i in range(n):
        w = [int(words[(3 * i + j) % len(words)]) for j in range(3)]
        if i % 3 == 0:
            q = BatchQuery.count([w[0]],
                                 budget=QueryBudget(max_rel_error=0.5,
                                                    floor_rate=0.1))
        elif i % 3 == 1:
            q = BatchQuery.boolean(
                parse_boolean([w[0], "or", w[1], "and", w[2]]),
                budget=QueryBudget(max_rel_error=0.6, floor_rate=0.1))
        else:
            q = BatchQuery.ranked(
                w, k=10, budget=QueryBudget(max_rel_error=0.6,
                                            max_latency_s=0.05,
                                            floor_rate=0.1))
        queries.append(q)
    return queries


@pytest.mark.parametrize("pressure,seeds", [(0.0, range(40, 48)),
                                            (1.0, range(48, 56))],
                         ids=["planned", "degraded"])
def test_budgeted_count_intervals_cover_the_exact_count(
        small_corpus, built_index, pressure, seeds):
    """The budget record's calibration: with budgets attached, the
    count queries' 95% intervals cover the exact full-scan count for
    at least 90% of answers, on a planned pass (pressure 0) and on a
    fully degraded one (pressure 1, every query at its floor rate).
    Eight passes an arm (128 count answers): one pass of 16 reads
    anywhere from 0.75 to 1.0 at this size."""
    from repro.runtime import RatePlanner
    rate = 0.15
    budgeted = _budgeted_mixed_workload(small_corpus)
    plain_queries = [dataclasses.replace(q, budget=None) for q in budgeted]
    truths = [r.estimate.value if q.kind == "count" else None
              for q, r in zip(plain_queries, QueryBatch(
                  small_corpus, built_index).execute(plain_queries, 1.0))]
    engine = QueryBatch(small_corpus, built_index,
                        planner=RatePlanner(small_corpus.n_shards), ci=True)
    # fit the planner's error curves on served unbudgeted batches, so
    # the budgeted pass plans from fitted dispersion rather than the
    # cold seed (which plans a census and makes coverage vacuous)
    for s in (33, 34):
        engine.execute(plain_queries, rate, rng=np.random.default_rng(s))
    covered = total = 0
    for s in seeds:
        res = engine.execute(budgeted, rate, rng=np.random.default_rng(s),
                             pressure=pressure)
        for q, r, truth in zip(budgeted, res, truths):
            if q.kind == "count":
                total += 1
                covered += bool(r.estimate.covers(truth))
        audit = engine.last_report.budget
        assert audit["pressure"] == pressure
        assert audit["budgeted"] == len(budgeted)
    if pressure == 1.0:
        assert audit["at_floor"] == len(budgeted)
    else:
        # planned from the fitted curves: not every query at a census
        assert min(audit["planned_rates"]) < 1.0
    assert total == len(seeds) * len(budgeted[::3])
    assert covered / total >= 0.9, f"coverage {covered}/{total}"


def test_ci_flag_adds_intervals_to_retrieval(small_corpus, built_index):
    """ci=True: boolean results carry a bootstrap count estimate,
    ranked results a top-k stability score in [0, 1]; ci=False leaves
    the estimate slot empty (legacy shape)."""
    queries = _mixed_queries()
    on = QueryBatch(small_corpus, built_index, ci=True).execute(
        queries, 0.4, rng=np.random.default_rng(9))
    off = QueryBatch(small_corpus, built_index).execute(
        queries, 0.4, rng=np.random.default_rng(9))
    for q, r_on, r_off in zip(queries, on, off):
        if q.kind == "bool":
            assert r_on.estimate is not None
            assert r_on.estimate.value >= 0.0
            assert r_off.estimate is None
        elif q.kind == "ranked":
            assert r_on.estimate is not None
            assert 0.0 <= r_on.estimate.value <= 1.0
            assert r_off.estimate is None
