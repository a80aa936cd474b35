"""Spans and counters inside the served path: the per-batch stage
seconds and scan counters on ``ExecutionReport``, the window's queue
split (head-of-line vs window delay) and stage sums in
``BatchWindow.stats``, and the ``emapprox.*`` profiler spans they ride
on, read back from a CPU trace."""
import dataclasses
import glob
import json
import os
import threading
import time
from types import SimpleNamespace

import numpy as np

from repro.core.queries import BatchQuery, QueryBatch, parse_boolean
from repro.core.queries.batch import STAGES
from repro.runtime import BatchWindow, ShardTaskExecutor
from repro.runtime.qcache import SemanticQueryCache
from repro.utils import spans

QS = [BatchQuery.count([5]), BatchQuery.count([3, 8]),
      BatchQuery.boolean(parse_boolean([3, "and", 8])),
      BatchQuery.ranked([3, 8, 11], k=5)]


def _kernel_index(built_index, corpus):
    """A doc-granular, kernel-backed index: planning waits on the
    device (the Pallas kernel runs in interpret mode off the TPU)."""
    return dataclasses.replace(built_index, granularity="doc",
                               use_kernel=True).attach_corpus(corpus)


# ----------------------------------------------------------------------
# the span helper
# ----------------------------------------------------------------------
def test_span_self_seconds_nest_and_skip_non_stages():
    with spans.batch(7, ("plan", "plan_wait", "scan")) as rec:
        with spans.span("emapprox.plan"):
            time.sleep(0.01)
            with spans.span("emapprox.plan.wait"):
                time.sleep(0.1)
        with spans.span("emapprox.scan"):
            with spans.span("emapprox.gather"):   # not a stage
                time.sleep(0.02)
    assert rec.seq == 7
    # plan's self seconds leave out the wait nested in it
    assert 0.01 <= rec.stages["plan"] < 0.1
    assert 0.1 <= rec.stages["plan_wait"]
    # gather's time stays with the scan around it
    assert 0.015 <= rec.stages["scan"]
    # outside a batch a span only annotates
    with spans.span("emapprox.plan") as ann:
        assert ann is not None


def test_spans_are_per_thread():
    seen = {}

    def other():
        with spans.span("emapprox.plan"):
            time.sleep(0.02)
        seen["done"] = True

    with spans.batch(1, ("plan",)) as rec:
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert seen == {"done": True} and not t.is_alive()
    assert rec.stages["plan"] == 0.0


# ----------------------------------------------------------------------
# ExecutionReport: stages and counters
# ----------------------------------------------------------------------
def test_report_holds_six_stages_within_elapsed(small_corpus, built_index):
    eng = QueryBatch(small_corpus, _kernel_index(built_index, small_corpus))
    eng.execute(QS, 0.4, rng=np.random.default_rng(3))
    first = eng.last_report.batch
    eng.execute(QS, 0.4, rng=np.random.default_rng(4))
    r = eng.last_report
    assert r.batch == first + 1
    assert set(r.stages) == set(STAGES)
    assert len(STAGES) == 8
    assert all(v >= 0.0 for v in r.stages.values())
    assert sum(r.stages.values()) <= r.elapsed_s
    # the work each stage names happened
    for key in ("plan", "plan_words", "plan_wait", "sample", "scan",
                "reduce", "reduce_topk"):
        assert r.stages[key] > 0.0, key
    assert r.stages["probe"] == 0.0          # no cache attached
    rec = json.loads(json.dumps(r.record()))
    assert rec["batch"] == r.batch
    assert set(rec["stages"]) == set(STAGES)
    assert set(rec["counters"]) == {"shard_visits", "plan_shards",
                                    "scan_cpu_us", "scan_sweep",
                                    "bool_words", "bool_plans",
                                    "topk_candidates", "topk_queries",
                                    "group_pairs"}


def test_mixed_batch_counts_retrieval_work(small_corpus, built_index):
    qs = QS + [BatchQuery.boolean(parse_boolean(
        ["(", 3, "or", 5, ")", "and", 11])),
               BatchQuery.ranked([5, 8], k=3)]
    eng = QueryBatch(small_corpus, built_index)
    results = eng.execute(qs, 0.4, rng=np.random.default_rng(6))
    r = eng.last_report
    # the Boolean words {3, 8} and {3, 5, 11}, scored once as a union
    assert r.counters["bool_words"] == 4
    assert r.counters["bool_plans"] == 1
    assert r.counters["topk_queries"] == 2
    # every document of every shard a ranked query drew is a candidate
    docs = [sum(len(small_corpus.shards[int(s)].doc_ids) for s in r.plan[i])
            for i, q in enumerate(qs) if q.kind == "ranked"]
    assert r.counters["topk_candidates"] == sum(docs) > 0
    assert r.stages["plan_words"] > 0.0 and r.stages["reduce_topk"] > 0.0
    assert len(results) == len(qs)


def test_count_only_batch_counts_no_retrieval_work(small_corpus,
                                                   built_index):
    eng = QueryBatch(small_corpus, built_index)
    eng.execute(QS[:2], 0.4, rng=np.random.default_rng(7))
    r = eng.last_report
    for key in ("bool_words", "bool_plans", "topk_candidates",
                "topk_queries"):
        assert r.counters[key] == 0, key
    assert r.stages["plan_words"] == 0.0 and r.stages["reduce_topk"] == 0.0


def test_counters_union_and_sum_of_plan(small_corpus, built_index):
    with ShardTaskExecutor(workers=2) as ex:
        eng = QueryBatch(small_corpus, built_index, executor=ex)
        eng.execute(QS, 0.5, rng=np.random.default_rng(5))
    r = eng.last_report
    union = set().union(*(set(int(s) for s in p) for p in r.plan))
    assert r.counters["shard_visits"] == len(union) > 0
    assert r.counters["plan_shards"] == sum(len(p) for p in r.plan)
    assert r.counters["plan_shards"] >= r.counters["shard_visits"]
    assert r.counters["scan_cpu_us"] >= 0


def test_all_cache_hit_batch_counts_no_visits(small_corpus, built_index):
    eng = QueryBatch(small_corpus, built_index, cache=SemanticQueryCache())
    qs = [BatchQuery.count([5]), BatchQuery.count([3, 8])]
    eng.execute(qs, 0.5, rng=np.random.default_rng(1))
    assert eng.last_report.counters["shard_visits"] > 0
    eng.execute(qs, 0.5, rng=np.random.default_rng(2))
    r = eng.last_report
    assert r.cache["hits"] == len(qs)
    assert r.counters["shard_visits"] == 0
    assert r.counters["plan_shards"] == 0
    assert r.stages["probe"] > 0.0 and r.stages["plan"] == 0.0


# ----------------------------------------------------------------------
# BatchWindow: queue split and stage sums
# ----------------------------------------------------------------------
class _SleepyEngine:
    """Stands in for QueryBatch: sleeps a fixed time per batch and
    reports fixed stages and counters."""

    def __init__(self, sleep_s: float):
        self.sleep_s = sleep_s
        self.started = threading.Event()
        self.n = 0
        self.last_report = None

    def execute(self, queries, rate, rng=None):
        self.started.set()
        time.sleep(self.sleep_s)
        self.n += 1
        self.last_report = SimpleNamespace(
            batch=self.n, cache=None,
            stages=dict(dict.fromkeys(STAGES, 0.0), scan=0.001),
            counters=dict(shard_visits=3, plan_shards=5, scan_cpu_us=700))
        return list(queries)


def test_query_behind_a_running_batch_accrues_head_of_line():
    eng = _SleepyEngine(0.3)
    with BatchWindow(eng, 0.5, max_batch=1, max_delay_s=0.0) as win:
        f1 = win.submit("a")
        assert eng.started.wait(timeout=10)
        time.sleep(0.1)
        f2 = win.submit("b")       # ~0.2 s of the first batch remain
        assert f1.result(timeout=10) == "a"
        assert f2.result(timeout=10) == "b"
    s = win.stats
    assert 150_000 <= s["queue_hol_us"] <= 300_000
    assert s["queue_delay_us"] < 50_000


def test_lone_query_accrues_only_window_delay():
    eng = _SleepyEngine(0.0)
    with BatchWindow(eng, 0.5, max_batch=100, max_delay_s=0.05) as win:
        assert win.submit("a").result(timeout=10) == "a"
    assert win.stats["queue_hol_us"] == 0
    assert 40_000 <= win.stats["queue_delay_us"] < 5_000_000


def test_window_counters_are_monotone_and_fold_reports():
    eng = _SleepyEngine(0.01)
    keys = ("queue_hol_us", "queue_delay_us", "scan_us", "plan_us",
            "scan_cpu_us", "shard_visits", "plan_shards", "batches")
    with BatchWindow(eng, 0.5, max_batch=2, max_delay_s=0.005) as win:
        snaps = [dict(win.stats)]
        for k in range(4):
            futs = [win.submit(i) for i in range(3)]
            [f.result(timeout=10) for f in futs]
            with win._wake:                 # a consistent read
                snaps.append(dict(win.stats))
    last = dict(win.stats)                  # every batch folded
    snaps.append(last)
    for a, b in zip(snaps, snaps[1:]):
        assert all(b[k] >= a[k] for k in keys)
    n = last["batches"]
    assert n >= 8
    assert last["shard_visits"] == 3 * n and last["plan_shards"] == 5 * n
    assert last["scan_cpu_us"] == 700 * n and last["scan_us"] == 1000 * n
    assert last["plan_us"] == 0


# ----------------------------------------------------------------------
# the profiler trace
# ----------------------------------------------------------------------
def test_profiler_trace_nests_stage_spans_under_execute(
        small_corpus, built_index, tmp_path):
    import jax
    from jax.profiler import ProfileData

    eng = QueryBatch(small_corpus, _kernel_index(built_index, small_corpus))
    eng.execute(QS, 0.4, rng=np.random.default_rng(3))     # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.execute(QS, 0.4, rng=np.random.default_rng(4))
    finally:
        jax.profiler.stop_trace()
    seq = eng.last_report.batch
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("emapprox.")]
            if evs:
                lines.append(evs)
    # every emapprox span of the batch is on the calling thread's line
    assert len(lines) == 1
    evs = lines[0]
    roots = [e for e in evs if e[0] == "emapprox.execute"]
    assert len(roots) == 1
    _, a, b, _ = roots[0]
    names = {e[0] for e in evs}
    assert {"emapprox.plan", "emapprox.plan.words", "emapprox.plan.wait",
            "emapprox.sample", "emapprox.scan", "emapprox.gather",
            "emapprox.reduce", "emapprox.reduce.topk"} <= names
    for name, s, e, stats in evs:
        assert stats.get("batch") == seq, name
        assert a <= s <= e <= b, name

    def inside(child, parent):
        (cs, ce), = [(s, e) for n, s, e, _ in evs if n == child][:1]
        return any(ps <= cs and ce <= pe
                   for n, ps, pe, _ in evs if n == parent)

    assert inside("emapprox.plan.wait", "emapprox.plan")
    assert inside("emapprox.plan.words", "emapprox.plan")
    assert inside("emapprox.gather", "emapprox.scan")
    assert inside("emapprox.reduce.topk", "emapprox.reduce")
