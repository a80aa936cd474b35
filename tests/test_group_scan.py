"""The group scan of a batch's word-driven queries (``WordScan`` over the
corpus-wide postings table, ``data/store.corpus_postings``) against the
per-shard tasks it replaces, bit for bit: the table's rows, the scan
on its own over many plans and queries, whole batches under the
calling-thread executor, the inline fallback and a host group, the
per-shard fault contract, and live ingest growing and adding shards
the table never saw."""
import numpy as np
import pytest

from repro.core.queries import BatchQuery, QueryBatch
from repro.core.queries.batch import WordScan
from repro.core.queries.retrieval import BoolExpr
from repro.data import store
from repro.data.store import (
    DocShard,
    Document,
    ShardedCorpus,
    corpus_postings,
    shard_postings,
)
from repro.runtime.executor import ShardTaskExecutor, ShardTaskError
from repro.runtime.placement import HostGroupExecutor, PlacementMap

W = BoolExpr.w


def _with_empties() -> ShardedCorpus:
    """Shards holding empty documents, one shard with no document at
    all, and one whose words stop short of the others' vocabulary."""
    rng = np.random.default_rng(3)
    groups, doc = [], 0
    for s in range(6):
        docs = []
        for _ in range(0 if s == 2 else 9):
            n = 0 if doc % 4 == 0 else int(rng.integers(1, 25))
            top = 12 if s == 4 else 40
            docs.append(Document(doc, rng.integers(0, top, n).astype(
                np.int32)))
            doc += 1
        groups.append(DocShard.from_documents(s, docs))
    return ShardedCorpus(groups, 64)


def _stats(corpus):
    """(doc_freq, n_docs, avg_len) as the engine takes them without an
    index."""
    df = np.zeros(corpus.vocab_size, np.int64)
    for s in corpus.shards:
        post = shard_postings(s)
        df[: post.indptr.shape[0] - 1] += np.diff(post.indptr)
    n = corpus.n_docs
    return df, n, corpus.n_tokens / max(n, 1)


def _words(corpus):
    """Frequent words, one absent from the corpus, and one present in
    the corpus but past the local vocabulary of some shard."""
    counts = np.zeros(corpus.vocab_size, np.int64)
    for s in corpus.shards:
        counts += np.bincount(s.tokens, minlength=corpus.vocab_size)
    top = [int(w) for w in np.argsort(-counts, kind="stable")[:5]]
    absent = int(np.nonzero(counts == 0)[0][0])
    local = [shard_postings(s).indptr.shape[0] - 1 for s in corpus.shards
             if s.n_docs]
    past = next(w for w in range(min(local), max(local)) if counts[w])
    return top, absent, past


def _queries(corpus):
    (a, b, c, d, e), absent, past = _words(corpus)
    return {
        "ranked-1": BatchQuery.ranked([a]),
        "ranked-2": BatchQuery.ranked([a, b]),
        "ranked-3": BatchQuery.ranked([c, a, b]),
        "ranked-4": BatchQuery.ranked([d, c, a, d]),
        "ranked-absent": BatchQuery.ranked([absent, b, 10**6]),
        "ranked-past": BatchQuery.ranked([past, c]),
        "bool-word": BatchQuery.boolean(W(b)),
        "bool-and": BatchQuery.boolean(W(a) & W(b)),
        "bool-or-and": BatchQuery.boolean((W(a) | W(b)) & W(c)),
        "bool-nested": BatchQuery.boolean(
            ((W(a) & W(d)) | (W(b) & W(e))) | (W(c) & (W(e) | W(past)))),
        "bool-absent": BatchQuery.boolean(W(absent) | (W(a) & W(10**6))),
        "bool-past": BatchQuery.boolean(W(past) & W(a)),
    }


QUERY_NAMES = [
    "ranked-1", "ranked-2", "ranked-3", "ranked-4", "ranked-absent",
    "ranked-past", "bool-word", "bool-and", "bool-or-and", "bool-nested",
    "bool-absent", "bool-past"]


def _plan(corpus, which):
    n = corpus.n_shards
    if which == "empty":
        return []
    if which == "one":
        return [n // 2]
    if which == "all":
        return list(range(n))
    return sorted(np.random.default_rng(5).choice(n, n // 2, replace=False)
                  .tolist())


def _assert_same(got, want):
    """Bit for bit: the same arrays with the same dtypes."""
    if isinstance(want, tuple):
        assert isinstance(got, tuple) and len(got) == len(want)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)
    else:
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


def _scan_vs_tasks(corpus, queries, plans):
    """Run ``queries`` (query index -> query) over ``plans`` (query
    index -> shard ids) through one group pass and through the
    per-shard tasks, and compare every (query, shard) result."""
    df, n_docs, avg = _stats(corpus)
    scan = WordScan(corpus, corpus_postings(corpus), queries, df, n_docs,
                    avg)
    queries_of = {}
    for qi, sids in plans.items():
        for sid in sids:
            queries_of.setdefault(sid, []).append(qi)
    out = scan.run_group(sorted(queries_of), queries_of)
    assert sorted(out) == sorted(queries_of)
    for sid, qis in queries_of.items():
        assert sorted(out[sid]) == sorted(qis)
        for qi in qis:
            want = QueryBatch._shard_fn(queries[qi], df, n_docs, avg)(
                corpus.shards[sid])
            _assert_same(out[sid][qi], want)
    assert scan.answered == [sum(len(p) for p in plans.values())]


# ----------------------------------------------------------------------
# the table
# ----------------------------------------------------------------------
@pytest.mark.parametrize("which", ["small", "empties"])
def test_table_rows_are_the_shard_postings(small_corpus, which):
    corpus = small_corpus if which == "small" else _with_empties()
    t = corpus_postings(corpus)
    assert corpus_postings(corpus) is t          # cached on the corpus
    assert t.pos.dtype == np.int32 and t.tf.dtype == np.int32
    assert t.doc_len.shape == t.shard_of.shape == (corpus.n_docs,)
    np.testing.assert_array_equal(
        t.doc_ids, np.concatenate([s.doc_ids for s in corpus.shards]))
    for w in range(corpus.vocab_size + 2):
        pos, tf = [], []
        for sid, s in enumerate(corpus.shards):
            docs, f = shard_postings(s).lookup(w)
            pos.append(docs.astype(np.int64) + t.shard_start[sid])
            tf.append(f)
        got_pos, got_tf = t.lookup(w)
        np.testing.assert_array_equal(got_pos, np.concatenate(pos))
        np.testing.assert_array_equal(got_tf, np.concatenate(tf))
    for sid, s in enumerate(corpus.shards):
        assert t.covers(sid, s)
        a, b = t.shard_start[sid], t.shard_start[sid + 1]
        assert (t.shard_of[a:b] == sid).all()
        np.testing.assert_array_equal(t.doc_len[a:b], np.diff(s.offsets))
    assert not t.covers(0, DocShard.from_documents(0, []))
    assert not t.covers(corpus.n_shards, corpus.shards[0])


# ----------------------------------------------------------------------
# the group pass against the per-shard tasks
# ----------------------------------------------------------------------
@pytest.mark.parametrize("plan", ["empty", "one", "all", "half"])
@pytest.mark.parametrize("name", QUERY_NAMES)
def test_group_pass_matches_shard_tasks(small_corpus, name, plan):
    q = _queries(small_corpus)[name]
    _scan_vs_tasks(small_corpus, {0: q},
                   {0: _plan(small_corpus, plan)})


@pytest.mark.parametrize("name", QUERY_NAMES)
def test_group_pass_over_empty_documents_and_shards(name):
    corpus = _with_empties()
    q = _queries(corpus)[name]
    _scan_vs_tasks(corpus, {0: q}, {0: list(range(corpus.n_shards))})


def test_group_pass_of_many_queries_over_their_own_plans(small_corpus):
    qs = _queries(small_corpus)
    rng = np.random.default_rng(9)
    n = small_corpus.n_shards
    queries = {i: qs[name] for i, name in enumerate(QUERY_NAMES)}
    plans = {i: sorted(rng.choice(n, int(rng.integers(1, n)),
                                  replace=False).tolist())
             for i in queries}
    _scan_vs_tasks(small_corpus, queries, plans)


# ----------------------------------------------------------------------
# whole batches through the executors
# ----------------------------------------------------------------------
def _batch(corpus, kind):
    (a, b, c, d, e), _, _ = _words(corpus)
    ranked = [BatchQuery.ranked([a, c, e], k=7), BatchQuery.ranked([b], k=3)]
    boolean = [BatchQuery.boolean((W(a) | W(b)) & W(c)),
               BatchQuery.boolean(W(d) & W(e))]
    counts = [BatchQuery.count([a]), BatchQuery.count([b, c])]
    return dict(ranked=ranked, bool=boolean,
                mixed=[counts[0]] + ranked[:1] + boolean[:1] + counts[1:]
                + ranked[1:])[kind]


def _executor(name, corpus, **kw):
    if name == "inline":
        return None
    if name == "hosts":
        return HostGroupExecutor(PlacementMap.blocked(corpus.n_shards, 3),
                                 workers_per_host=1, **kw)
    return ShardTaskExecutor(workers=2, **kw)


def _serve(corpus, index, queries, rate, seed, *, group, executor=None):
    """One batch, by the group scan or (``group=False``) by today's
    per-shard tasks alone; returns (results, report)."""
    eng = QueryBatch(corpus, index, executor=executor)
    if not group:
        eng._word_scan = lambda *a: None
    out = eng.execute(queries, rate, rng=np.random.default_rng(seed))
    return out, eng.last_report


def _assert_same_answers(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_equal(g._replace(elapsed_s=0.0)._asdict(),
                                w._replace(elapsed_s=0.0)._asdict())


@pytest.mark.parametrize("kind", ["ranked", "bool", "mixed"])
@pytest.mark.parametrize("ex", ["sweep", "inline", "hosts"])
def test_batch_answers_match_per_shard_path(small_corpus, built_index, ex,
                                            kind):
    qs = _batch(small_corpus, kind)
    runs = {}
    for group in (True, False):
        executor = _executor(ex, small_corpus)
        try:
            runs[group] = _serve(small_corpus, built_index, qs, 0.4, 21,
                                 group=group, executor=executor)
        finally:
            if executor is not None:
                executor.close()
    (got, rep), (want, ref) = runs[True], runs[False]
    _assert_same_answers(got, want)
    for a, b in zip(rep.plan, ref.plan):
        np.testing.assert_array_equal(a, b)
    word = sum(len(p) for p, q in zip(rep.plan, qs) if q.kind != "count")
    assert rep.counters["group_pairs"] == word > 0
    assert ref.counters["group_pairs"] == 0
    if ex != "inline":
        assert rep.counters["scan_sweep"] == 1


def test_a_batch_without_word_queries_takes_no_group_route(small_corpus,
                                                           built_index):
    counts = [q for q in _batch(small_corpus, "mixed") if q.kind == "count"]
    with ShardTaskExecutor() as ex:
        _, rep = _serve(small_corpus, built_index, counts, 0.4, 3,
                        group=True, executor=ex)
        assert rep.counters["group_pairs"] == 0
        assert ex.last_job["median_task_s"] > 0.0


def test_host_group_runs_one_group_per_host(small_corpus, built_index):
    qs = _batch(small_corpus, "ranked") + _batch(small_corpus, "bool")
    calls = []
    with _executor("hosts", small_corpus) as ex:
        eng = QueryBatch(small_corpus, built_index, executor=ex)
        orig = eng._word_scan

        def word_scan(*a):
            scan = orig(*a)
            run = scan.run_group

            def run_group(shard_ids, queries_of):
                calls.append(sorted(shard_ids))
                return run(shard_ids, queries_of)
            scan.run_group = run_group
            return scan
        eng._word_scan = word_scan
        eng.execute(qs, 0.4, rng=np.random.default_rng(8))
        split = ex.residency_split(eng.last_report.plan)
        placement = ex.placement
    assert sorted(len(c) for c in calls) == sorted(
        n for n in split.values() if n)
    for c in calls:
        assert len({placement.hosts_of(s)[0] for s in c}) == 1


# ----------------------------------------------------------------------
# the per-shard fault contract on the group route
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["ranked", "mixed"])
def test_hooks_fire_once_per_shard_and_attempt(small_corpus, built_index,
                                               kind):
    qs = _batch(small_corpus, kind)
    seen = {}
    for group in (True, False):
        faults, tasks = [], []
        with ShardTaskExecutor(
                fault_hook=lambda s, a: faults.append((s, a)),
                task_hook=lambda s, a, j: tasks.append((s, a, j))) as ex:
            _, rep = _serve(small_corpus, built_index, qs, 0.4, 4,
                            group=group, executor=ex)
        union = sorted({int(s) for p in rep.plan for s in p})
        assert sorted(faults) == [(s, 1) for s in union]
        assert sorted(tasks) == [(s, 1, 0) for s in union]
        seen[group] = sorted(faults)
    assert seen[True] == seen[False]


def _first_planned_shard(corpus, index, qs, seed):
    _, rep = _serve(corpus, index, qs, 0.4, seed, group=False)
    return int(rep.plan[0][0])


@pytest.mark.parametrize("kind", ["ranked", "bool", "mixed"])
def test_a_flaky_shard_is_retried_alone(small_corpus, built_index, kind):
    qs = _batch(small_corpus, kind)
    bad = _first_planned_shard(small_corpus, built_index, qs, 12)
    calls = []

    def flaky(sid, attempt):
        calls.append((sid, attempt))
        if sid == bad and attempt == 1:
            raise RuntimeError("injected")

    with ShardTaskExecutor(fault_hook=flaky, max_retries=2) as ex:
        got, rep = _serve(small_corpus, built_index, qs, 0.4, 12,
                          group=True, executor=ex)
        assert ex.stats["retries"] == 1
    want, _ = _serve(small_corpus, built_index, qs, 0.4, 12, group=False)
    _assert_same_answers(got, want)
    assert sorted(a for s, a in calls if s == bad) == [1, 2]
    assert all(a == 1 for s, a in calls if s != bad)
    assert rep.counters["group_pairs"] > 0


@pytest.mark.parametrize("kind", ["ranked", "bool", "mixed"])
def test_a_failing_shard_is_lost_alone_under_partial(small_corpus,
                                                     built_index, kind):
    qs = _batch(small_corpus, kind)
    bad = _first_planned_shard(small_corpus, built_index, qs, 13)

    def dead(sid, attempt):
        if sid == bad:
            raise RuntimeError("injected")

    runs = {}
    for group in (True, False):
        with ShardTaskExecutor(fault_hook=dead, max_retries=1,
                               allow_partial=True) as ex:
            runs[group] = _serve(small_corpus, built_index, qs, 0.4, 13,
                                 group=group, executor=ex)
            assert ex.last_job["lost_shards"] == 1.0
            assert ex.stats["retries"] == 1
    (got, rep), (want, ref) = runs[True], runs[False]
    _assert_same_answers(got, want)
    assert rep.degraded == ref.degraded
    assert rep.degraded["lost_shards"] == 1
    assert rep.degraded["lost_per_query"][0] == 1
    assert got[0].lost_shards == 1


def test_a_failing_shard_raises_without_partial(small_corpus, built_index):
    qs = _batch(small_corpus, "ranked")
    bad = _first_planned_shard(small_corpus, built_index, qs, 14)

    def dead(sid, attempt):
        if sid == bad:
            raise RuntimeError("injected")

    with ShardTaskExecutor(fault_hook=dead, max_retries=1) as ex:
        with pytest.raises(ShardTaskError, match=f"shard {bad} failed"):
            _serve(small_corpus, built_index, qs, 0.4, 14, group=True,
                   executor=ex)


# ----------------------------------------------------------------------
# live ingest: shards the table never saw answer per shard
# ----------------------------------------------------------------------
def test_ingest_grown_and_spilled_shards_answer_per_shard(
        small_corpus, built_index, monkeypatch):
    corpus = ShardedCorpus(list(small_corpus.shards),
                           small_corpus.vocab_size)
    eng = QueryBatch(corpus, built_index, executor=ShardTaskExecutor())
    table = corpus._postings_table
    assert table is not None and table.shards == tuple(corpus.shards)
    (a, b, c, d, e), _, _ = _words(corpus)
    rng = np.random.default_rng(17)
    new = [rng.choice([a, b, c, d, e], 30).astype(np.int32)
           for _ in range(6)]
    last = corpus.n_shards - 1
    grown, _, affected = corpus.append_documents(
        new, shard_tokens=corpus.shards[last].n_tokens + 10)
    assert affected == [last, last + 1] and grown.n_shards == last + 2

    def no_build(_):
        raise AssertionError("the postings table was rebuilt")
    monkeypatch.setattr(store, "build_corpus_postings", no_build)
    eng.swap_world(grown, built_index)
    assert grown._postings_table is table
    assert [_covers(eng, s) for s in (0, last, last + 1)] == [
        True, False, False]

    qs = _batch(grown, "ranked") + _batch(grown, "bool")
    got = eng.execute(qs, 1.0)
    rep = eng.last_report
    want, _ = _serve(grown, built_index, qs, 1.0, 0, group=False)
    _assert_same_answers(got, want)
    assert rep.counters["group_pairs"] == len(qs) * last
    assert grown._postings_table is table
    eng.executor.close()


def _covers(eng, sid):
    """Whether the engine's group scan answers shard ``sid`` itself."""
    scan = eng._word_scan(eng.corpus, [BatchQuery.ranked([1])], None, 1,
                          1.0)
    return scan.covers(sid)
