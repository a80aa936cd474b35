"""Serving throughput: batched engine vs per-query execution.

Measures queries/sec and p50 latency for five execution modes of the
same mixed workload (aggregation / Boolean / ranked, paper Table I):

  per_query_scan  - legacy path: one query at a time, per-shard
                    operators rescan the flat token arrays (the
                    pre-postings serving path, kept via the *_scan
                    parity references)
  per_query       - one query at a time through the current
                    single-query entry points (postings-backed)
  batched         - ``QueryBatch``: one-pass batched scoring, shared
                    shard scans, per-shard postings
  batched_fused   - ``QueryBatch`` with doc-granular scoring enabled:
                    planning scores every query against every *doc*
                    and reduces to shards through the fused path
                    (shard-sorted ``np.add.reduceat`` on CPU; the
                    segment-sum Pallas kernels on TPU) — n_docs >>
                    n_shards of scoring work at batched-row throughput
  windowed        - ``BatchWindow`` frontend over the batched engine:
                    queries submitted one at a time in an open-loop
                    burst, windows closed by deadline (2 ms) or size.
                    This is a *saturated-throughput* row: its
                    ``p50_sojourn_ms`` includes dispatcher queue
                    backlog, so it is comparable run-to-run but is NOT
                    the lightly-loaded window latency (for that, see
                    examples/serve_queries.py, which paces arrivals)
  batched_hostsN  - (``--hosts N``; the smoke gate runs N=2) the
                    batched engine through a simulated N-host topology:
                    a blocked ``PlacementMap`` over the shards and a
                    ``HostGroupExecutor`` splitting every union plan by
                    residency, per-host shared scans, cross-host
                    gather.  Worker threads are held at the single-host
                    total so the row isolates placement overhead, not
                    parallelism.  Alongside the timing row the bench
                    emits a ``placement`` record and *hard-checks* the
                    locality contract: per-host scan counts must equal
                    the residency split of every union plan, and the
                    gathered results must be identical to the
                    single-executor path for all three query types.
                    Read the throughput ratio on the *smoke* config
                    (dispatch-dominated batches — the CI gate): there
                    it shows the no-cross-host-penalty property.  At
                    full-bench scale the simulation undercounts: both
                    hosts' scans are GIL-bound numpy on ONE machine,
                    so their "concurrent" halves partly serialize and
                    the ratio dips below 1.0 — contention a real pod,
                    with per-host cores, does not have (the same
                    effect already makes the single-host arm faster at
                    1 worker than 2 on this container)
  batched_lbN     - the *hot-host* arm (runs whenever ``--hosts`` is
                    active): same N-host topology, but host 0 is
                    artificially degraded (the injection hook sleeps
                    ``HOT_HOST_DELAY_S`` per resident shard before
                    each of its scans) and the executor runs with the
                    replica-aware balancer on.  The warm pass teaches
                    the load model that host 0 is hot; measured trials
                    then run the balanced split, which sheds host 0's
                    shard groups onto their ring replicas (scans stay
                    local — replicas hold the data).  Alongside the
                    timing row the bench emits a ``balance`` record
                    (estimated vs realized per-host makespan, shed
                    counts, primary-vs-balanced makespan) and
                    *hard-fails* unless (a) balanced results are
                    bit-for-bit the single-executor results, (b)
                    balanced and primary-only splits gather
                    identically, and (c) the balanced split reduces
                    the mean job makespan vs the primary-only split
                    under the same hot host
  batched_budget  - the error-budgeted engine: every query carries a
                    ``QueryBudget`` (error / latency SLOs), a
                    ``RatePlanner`` picks its per-query sampling rate,
                    and Boolean/ranked results gain bootstrap CIs.
                    The row prices budget planning + per-result CIs on
                    the batched hot path; it is floored by the
                    regression gate.  Alongside it (whenever
                    ``--hosts`` is active) a ``budget`` record runs
                    three *hard checks*: (1) a planner-bearing engine
                    serving unbudgeted queries is bit-for-bit the
                    plain engine, at the nominal rate and at the
                    precise rate-1.0 fast path; (2) on a deterministic
                    untimed pass (pinned rng, pressure 0 and fully
                    degraded) the count queries' 95% CIs cover the
                    exact full-scan answer for >= 90% of queries;
                    (3) under ~3x-capacity Poisson arrivals with the
                    hot host, the budget-aware window (degradation
                    ladder on) sheds strictly fewer queries than the
                    static-backpressure baseline on the same arrival
                    schedule and queue bound — and the baseline must
                    itself shed, or the arm failed to overload
  batched_chaos   - (``--chaos``; always on under ``--smoke``) the
                    batched engine through the 2-host topology under a
                    steady scripted ``FaultPlan``: every host uniformly
                    slowed ``CHAOS_SLOW_MS`` per shard scan (the row is
                    sleep-dominated, hence machine-stable and floorable
                    by the regression gate) and host 1 mildly flaky
                    (deterministic seeded task faults, cleared by the
                    executor's retry path).  Alongside it the bench
                    emits a ``chaos`` record: an untimed scripted
                    kill -> serve-degraded -> join -> recover -> drain
                    scenario through ``FleetManager`` that *hard-fails*
                    unless zero queries are lost, every batch (faulted
                    ones included) gathers bit-for-bit the
                    single-executor results, the post-join makespan
                    recovers to within 1.25x the pre-crash baseline,
                    the joiner was fully warmed before serving, and the
                    planned drain orphans nothing (``--chaos-only``
                    runs just this arm — the CI chaos-smoke job)
  batched_zipf    - (``--zipf``; always on under ``--smoke``) the plain
                    batched engine serving a Zipf-skewed stream (skew
                    ``ZIPF_SKEW``, 2x the distinct pool): repeated hot
                    queries pay full sampling + scan price every time.
                    The uncached baseline the cached row is read
                    against — same stream, so qps/p50 are over the
                    stream length, not ``n_queries``
  batched_cached  - the same Zipf stream through a
                    ``SemanticQueryCache``-enabled engine (built via
                    ``launch.serve_stack.build_serving_stack``, reused
                    across trials): the warm pass populates the cache,
                    measured trials serve mostly exact LSH-signature
                    hits that skip sampling, scanning, and the
                    executor entirely.  Floored by the regression
                    gate, and *hard-gated* in-run: cached p50 must be
                    strictly below ``batched_zipf`` p50.  Alongside
                    the rows a ``cache`` record runs two untimed hard
                    checks at Hamming radius 0 — (1) exact-hit
                    parity: a cold cached pass is bit-for-bit the
                    plain engine under the same seeds, and a warm
                    pass under different seeds resolves every query
                    from the cache with results bit-for-bit the cold
                    ones; (2) generation fencing: across scripted
                    ``FleetManager`` join and drain swaps ZERO cache
                    hits cross the placement-epoch bump, every entry
                    drops as ``stale_epoch``, and post-swap results
                    match a plain engine on the new topology
  batched_ingest  - (``--ingest``; always on under ``--smoke``) the
                    batched engine through an ingest-enabled serving
                    stack while a small ``Ingestor.step`` — corpus
                    append, frozen-model PV-DBOW inference, RCU
                    generation swap — races every call from a
                    background thread.  The row prices writer
                    contention on the serving hot path and is floored
                    by the regression gate.  Alongside it a
                    hard-gated ``ingest`` record appends 25% of the
                    corpus (sentinel-phrase docs) mid-serve and
                    fails unless: zero queries shed/lose shards
                    across the swap; every racing batch is
                    bit-for-bit either the pre-append or post-append
                    reference (never a torn world); the post-swap
                    sentinel census observes exactly the appended
                    docs at error bound 0 with the content
                    generation minted exactly once; serving p99 with
                    ingest active stays within 1.25x the no-ingest
                    p99; and a warm semantic cache serves ZERO hits
                    across the content bump, dropping every entry as
                    ``stale_epoch`` (the content-axis fence the old
                    placement-only epoch could not see)
  batched_mega    - the one-launch scan-over-shards megakernel row:
                    every query in the chunk scans the FULL fleet (the
                    high-shards-per-host regime), and the chunk's scan
                    fns come from one ``MegascanSpec``, so the executor
                    routes each chunk as ONE Pallas launch over the
                    packed multi-shard payload (double-buffered shard
                    prefetch on TPU) instead of one task per shard.
                    Floored by the regression gate — the row collapses
                    if the megakernel route stops engaging and the
                    scan falls back to per-shard dispatch.  Alongside
                    it a hard-gated ``megascan`` record checks
                    bit-for-bit group-vs-per-shard gather parity on
                    ragged plans (sum AND ranked modes, single- and
                    host-group executors), that the one-launch wall
                    beats the per-shard route's on the same plan, and
                    that the roofline dispatch share drops — the
                    dispatch-bound -> bandwidth-bound claim as a
                    rendered row (``python -m benchmarks.roofline
                    --serve BENCH_serve.json``)

Each mode runs ``trials`` times and the best wall time is reported
(the container CPU is shared; best-of filters scheduler noise).
Emits ``BENCH_serve.json`` (path overridable via ``BENCH_SERVE_JSON``)
so future PRs have a serving-perf trajectory to compare against.
NOTE: the trajectory *resets at PR 3* — retrieval queries now read
``ceil(rate * n_shards)`` distinct shards (``pps_sample_distinct``)
instead of a with-replacement multiset that often touched far fewer,
so every bool/ranked query in every arm does more scan work at the
same nominal rate; the ~35% drop in the ``batched``/``batched_fused``
rows vs PR 2 is that extra work, not a runtime regression.  Rows are
comparable from PR 3 onward.

``--sweep`` additionally drives a *load sweep*: Poisson arrivals
(exponential gaps, TextBenDS-style throughput emulation) at several
rates spanning light load to past dispatcher capacity, each served
three ways — the static (2 ms, fixed-size) window, the adaptive
``WindowController`` window, and the error-budgeted engine behind an
adaptive window with a bounded queue — and records per-rate p50/p99
sojourn rows under ``load_sweep`` in the JSON, each row carrying the
fraction of queries shed vs served degraded and the realized p90
relative error of its count queries against exact answers.  The
adaptive window must be no worse at both ends: at light load it
collapses the deadline (a lone query stops waiting out 2 ms), at heavy
load it grows the batch (amortization is what keeps the dispatcher
stable); the budget mode is where overload walks the
degrade-before-shed ladder instead of queueing without bound.

  PYTHONPATH=src python -m benchmarks.serve_bench [--smoke] [--sweep]

``--smoke`` runs a small corpus + short training in well under a
minute — the CI serving smoke job.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import threading
import time

import numpy as np

from benchmarks.common import csv_row, pick_query_words, text_setup
from repro.kernels.common import on_tpu

# per-resident-shard delay injected on host 0 in the hot-host arm:
# several times the real per-shard scan cost at *both* bench scales
# (sub-ms on the smoke corpus, ~2-8 ms/shard at full scale on a loaded
# container), so the hot/cold cost ratio clears the balancer's
# hysteresis band decisively everywhere — a marginal ratio would make
# the shed (and the makespan hard-check) flap with container noise —
# yet cheap enough that the whole arm stays in CI budget (the primary
# arm pays it on ~half the union per job; the balanced arm sheds it)
HOT_HOST_DELAY_S = 1e-2

# uniform per-shard sleep injected on EVERY host in the chaos arms
# (FaultPlan.slow): it makes job makespans sleep-dominated, so the
# kill/recover makespan ratios and the batched_chaos row's throughput
# are set by the scripted scenario, not by container CPU speed — the
# property that lets the regression gate floor the chaos row and the
# recovery hard-check run with a tight 1.25x bound
CHAOS_SLOW_MS = 3.0

# the Zipf/cached arms' traffic shape: skew 1.5 makes the top query
# ~10x the 10th-ranked one (a realistic hot-query head), and the
# stream runs 2x the distinct pool so the cached arm's measured trials
# serve mostly repeats — the regime the semantic cache is built for
ZIPF_SKEW = 1.5
ZIPF_STREAM_FACTOR = 2

# the live-ingest arms: the hard-gated record appends a mid-run batch
# of INGEST_FRACTION * n_docs sentinel-phrase docs through one
# Ingestor.step racing the serving loop, and the batched_ingest timed
# row serves the pool while a small INGEST_CHUNK_DOCS step runs
# concurrently on every call — the row prices writer/reader GIL
# contention on the hot path, and the regression gate floors it.
# INGEST_P99_MAX_RATIO is the freshness-vs-latency contract from the
# record: serving p99 with ingest active may not exceed 1.25x the
# no-ingest p99.  Both arms serve the pool INGEST_GATE_PASSES times
# per trial (best-of-trials) and the p99 is over per-query samples —
# enough mass that the statistic is a real tail quantile (on a few
# batches "p99" is just the max, i.e. pure scheduler noise) and the
# step's startup burst (one batch in ~130) sits past the cutoff, so
# the gate measures steady-state racing, not the single worst
# collision.
INGEST_FRACTION = 0.25
INGEST_CHUNK_DOCS = 8
INGEST_INFER_STEPS = 10
INGEST_P99_MAX_RATIO = 1.25
INGEST_GATE_PASSES = 32


def _hot_host_hook(host, shard_ids):
    """Degrade host 0 by HOT_HOST_DELAY_S per shard it is about to
    scan — the straggler the balancer exists to route around."""
    if host == 0:
        time.sleep(HOT_HOST_DELAY_S * len(shard_ids))


def _mixed_queries(corpus, n, rng):
    from repro.core.queries import BatchQuery, parse_boolean
    words = pick_query_words(corpus, 3 * n, rng)
    if len(words) < 3:
        raise ValueError("corpus has too few mid-frequency candidate words "
                         f"for the serve bench ({len(words)} < 3)")
    qs = []
    for i in range(n):
        # pick_query_words caps at the candidate-pool size; recycle by
        # modulo so large n_queries never indexes past the end
        w = [int(words[(3 * i + j) % len(words)]) for j in range(3)]
        kind = i % 3
        if kind == 0:
            qs.append(BatchQuery.count([w[0]]))
        elif kind == 1:
            qs.append(BatchQuery.boolean(
                parse_boolean([w[0], "or", w[1], "and", w[2]])))
        else:
            qs.append(BatchQuery.ranked(w, k=10))
    return qs


def _zipf_stream(queries, n_stream, skew, rng):
    """Power-law query stream over the distinct pool: the i-th query
    (rank i+1) is drawn with probability proportional to rank**-skew —
    the hot/near-duplicate traffic shape real serving sees, and what
    the semantic cache is for."""
    ranks = np.arange(1, len(queries) + 1, dtype=np.float64)
    p = ranks ** -float(skew)
    p /= p.sum()
    idx = rng.choice(len(queries), size=int(n_stream), p=p)
    return [queries[int(i)] for i in idx]


def _run_per_query(corpus, index, queries, rate, executor, seed):
    """Current single-query entry points, one query at a time."""
    from repro.core.queries import (boolean_query, phrase_count_query,
                                    ranked_query)
    rng = np.random.default_rng(seed)
    lat = []
    for q in queries:
        t0 = time.perf_counter()
        if q.kind == "count":
            phrase_count_query(corpus, index, q.phrase, rate, rng=rng,
                               executor=executor)
        elif q.kind == "bool":
            boolean_query(corpus, index, q.expr, rate, rng=rng,
                          executor=executor)
        else:
            ranked_query(corpus, index, q.words, rate, k=q.k, rng=rng,
                         executor=executor)
        lat.append(time.perf_counter() - t0)
    return lat


def _run_per_query_scan(corpus, index, queries, rate, executor, seed):
    """The pre-postings serving path: single-query planning + flat-scan
    per-shard operators (``*_scan`` parity references)."""
    from repro.core.queries.retrieval import (_expr_eval_docs_scan,
                                              _expr_shard_similarity,
                                              bm25_scores_for_shard_scan)
    from repro.core.sampling import (ht_estimate, pps_sample,
                                     pps_sample_distinct,
                                     similarity_probabilities, unique_shards)
    from repro.data.store import count_phrase_in_shard
    rng = np.random.default_rng(seed)
    lat = []
    for q in queries:
        t0 = time.perf_counter()
        if q.kind == "bool":
            sims = _expr_shard_similarity(q.expr, index)
            probs = similarity_probabilities(sims)
        else:
            probs = index.shard_probabilities(
                q.phrase if q.kind == "count" else q.words)
        # same kind-dependent samplers as the engine paths: retrieval
        # reads distinct shards, aggregation keeps the HH multiset
        if q.kind == "count":
            sample = pps_sample(probs, rate, rng)
        else:
            sample = pps_sample_distinct(probs, rate, rng)
        distinct = unique_shards(sample)
        if q.kind == "count":
            by = executor.map_shards(
                corpus, distinct,
                lambda s, q=q: count_phrase_in_shard(s, q.phrase))
            local = np.asarray([by[int(s)] for s in sample.shard_ids],
                               np.float64)
            ht_estimate(local, sample)
        elif q.kind == "bool":
            executor.map_shards(
                corpus, distinct,
                lambda s, q=q: s.doc_ids[_expr_eval_docs_scan(q.expr, s)])
        else:
            by = executor.map_shards(
                corpus, distinct,
                lambda s, q=q: (s.doc_ids, bm25_scores_for_shard_scan(
                    s, q.words, index.doc_freq, index.n_docs,
                    index.avg_doc_len)))
            sc = np.concatenate([by[int(s)][1] for s in distinct])
            np.argsort(-sc, kind="stable")[:q.k]
        lat.append(time.perf_counter() - t0)
    return lat


def _run_batched(corpus, index, queries, rate, executor, seed, batch_size,
                 engine=None):
    from repro.core.queries import QueryBatch
    if engine is None:
        engine = QueryBatch(corpus, index, executor=executor)
    rng = np.random.default_rng(seed)
    lat = []
    for i in range(0, len(queries), batch_size):
        chunk = queries[i:i + batch_size]
        t0 = time.perf_counter()
        engine.execute(chunk, rate, rng=rng)
        lat.append((time.perf_counter() - t0, len(chunk)))
    return lat


def _mega_chunks(corpus, index, n, batch_size, rng):
    """Pre-built ``(fns, plan)`` chunks for the batched_mega arm: every
    query is a similarity-mass scan over the FULL fleet (the
    high-shards-per-host regime the megakernel targets), and each
    chunk's fns come from one ``MegascanSpec`` — so the megakernel
    route runs the chunk as ONE launch (per host on a host-group
    executor) where the per-shard route pays ``n_shards`` tasks.
    Built once and reused across trials, like the budget/cache engines:
    the warm pass is where payload packing and jit land."""
    from repro.kernels.megascan import MegascanSpec
    words = pick_query_words(corpus, 3 * n, rng)
    all_shards = list(range(corpus.n_shards))
    chunks = []
    for i in range(0, n, batch_size):
        m = min(batch_size, n - i)
        triples = [[int(words[(3 * (i + j) + t) % len(words)])
                    for t in range(3)] for j in range(m)]
        spec = MegascanSpec(index, index.query_vectors(triples))
        chunks.append((spec.scan_fns(), [all_shards] * m))
    return chunks


def _run_mega(corpus, chunks, executor, seed):
    """The one-launch scan arm: each pre-built chunk goes through
    ``map_shard_batch(megakernel=True)``."""
    lat = []
    for fns, plan in chunks:
        t0 = time.perf_counter()
        executor.map_shard_batch(corpus, plan, fns, megakernel=True)
        lat.append((time.perf_counter() - t0, len(plan)))
    return lat


def _run_windowed(corpus, index, queries, rate, executor, seed, batch_size,
                  window_s=0.002):
    """BatchWindow frontend: queries arrive one by one; windows close by
    deadline or size.  Latency is per-query sojourn (submit -> done)."""
    from repro.core.queries import QueryBatch
    from repro.runtime import BatchWindow
    engine = QueryBatch(corpus, index, executor=executor)
    window = BatchWindow(engine, rate, max_batch=batch_size,
                         max_delay_s=window_s,
                         rng=np.random.default_rng(seed))
    done_at = [None] * len(queries)
    submit_at = [None] * len(queries)

    def on_done(i):
        def cb(_fut):
            done_at[i] = time.perf_counter()
        return cb

    futs = []
    for i, q in enumerate(queries):
        submit_at[i] = time.perf_counter()
        fut = window.submit(q)
        fut.add_done_callback(on_done(i))
        futs.append(fut)
    for f in futs:
        f.result()
    window.close()
    return [(d - s, 1) for s, d in zip(submit_at, done_at)]


def _run_paced_window(corpus, index, queries, rate, executor, seed,
                      arrival_qps, *, adaptive, static_delay_s,
                      static_batch, max_batch_bound, max_pending=None,
                      budget=False):
    """One load-sweep arm: Poisson arrivals at ``arrival_qps`` through a
    static or adaptive window; returns (sojourns, realized_qps, stats,
    mean_batch, extras).

    With ``max_pending`` the submit loop is *shed-tolerant*: a
    ``Backpressure`` drops that query on the floor (the open-loop source
    does not retry — offered load is the experiment variable) and the
    query's slot in ``extras['results']`` stays ``None``.  With
    ``budget=True`` the engine carries a ``RatePlanner`` wired to the
    window's controller (``ci=True``), so queries with ``QueryBudget``s
    plan their own rates and overload degrades before it sheds."""
    from repro.core.queries import QueryBatch
    from repro.runtime import (Backpressure, BatchWindow, ControllerConfig,
                               RatePlanner, WindowController)
    controller = None
    if adaptive or budget:
        controller = WindowController(ControllerConfig(
            min_delay_s=1e-4, max_delay_s=0.02,
            min_batch=1, max_batch=max_batch_bound))
    planner = (RatePlanner(corpus.n_shards, controller=controller)
               if budget else None)
    engine = QueryBatch(corpus, index, executor=executor,
                        planner=planner, ci=budget)
    window = BatchWindow(engine, rate,
                         max_batch=(max_batch_bound if adaptive or budget
                                    else static_batch),
                         max_delay_s=static_delay_s,
                         controller=controller,
                         max_pending=max_pending,
                         rng=np.random.default_rng(seed))
    gap_rng = np.random.default_rng(seed + 7)
    n = len(queries)
    submit_at = [None] * n
    done_at = [None] * n
    retry_hints = []

    def on_done(i):
        def cb(_fut):
            done_at[i] = time.perf_counter()
        return cb

    t0 = time.perf_counter()
    futs = [None] * n
    for i, q in enumerate(queries):
        submit_at[i] = time.perf_counter()
        try:
            fut = window.submit(q)
        except Backpressure as bp:
            if bp.retry_after_s is not None:
                retry_hints.append(bp.retry_after_s)
        else:
            fut.add_done_callback(on_done(i))
            futs[i] = fut
        gap = gap_rng.exponential(1.0 / arrival_qps)
        # spin for sub-ms gaps: time.sleep() overshoots by ~100 us,
        # which at heavy load would silently throttle the target rate
        if gap > 1e-3:
            time.sleep(gap)
        else:
            t_next = submit_at[i] + gap
            while time.perf_counter() < t_next:
                pass
    results = [f.result() if f is not None else None for f in futs]
    wall = time.perf_counter() - t0
    window.close()
    served = [i for i, f in enumerate(futs) if f is not None]
    sojourns = np.asarray([done_at[i] - submit_at[i] for i in served]
                          or [0.0])
    batches = max(window.stats["batches"], 1)
    extras = dict(offered=n, served=len(served),
                  shed=window.stats["shed"],
                  escalated=window.stats["escalated"],
                  degraded=window.stats["degraded"],
                  retry_hints=retry_hints, results=results,
                  last_budget=window.last_budget)
    return (sojourns, len(served) / wall, dict(window.stats),
            len(served) / batches, extras)


def _result_matches(q, got, want) -> bool:
    """Bit-for-bit result equality per query kind — the one parity
    predicate both the placement and balance smoke gates enforce."""
    if q.kind == "count":
        return (got.estimate.value == want.estimate.value
                and got.estimate.error_bound == want.estimate.error_bound)
    if q.kind == "bool":
        return bool(np.array_equal(got.doc_ids, want.doc_ids))
    return bool(np.array_equal(got.doc_ids, want.doc_ids)
                and np.array_equal(got.scores, want.scores))


def _gather_parity(queries, got, want) -> dict:
    """{kind: all-match} over a batch of (query, got, want) triples."""
    parity = {"count": True, "bool": True, "ranked": True}
    for q, g, w in zip(queries, got, want):
        parity[q.kind] &= _result_matches(q, g, w)
    return parity


def _mega_scan_equal(got, want) -> bool:
    """Bit-for-bit equality of one query's per-shard scan dict — python
    floats in sum mode, ``{doc_ids, values}`` arrays in ranked mode."""
    if got.keys() != want.keys():
        return False
    for s, g in got.items():
        w = want[s]
        if isinstance(g, dict):
            if not (np.array_equal(g["doc_ids"], w["doc_ids"])
                    and np.array_equal(g["values"], w["values"])):
                return False
        elif g != w:
            return False
    return True


def _megascan_report(corpus, index, n_hosts, workers, batch_size) -> dict:
    """The one-launch megascan record (hard-gated).

    Untimed parity checks plus a timed dispatch-amortization micro:

      1. group-vs-per-shard parity: ``map_shard_batch(megakernel=True)``
         must gather BIT-FOR-BIT what ``megakernel=False`` (the
         per-shard fused path) gathers, on ragged plans — lone-shard
         queries, strict subsets, the full fleet — in sum mode AND
         ranked top-k mode.  The block-aligned payload pads every shard
         independently, so partials must not move across groupings.
      2. host-group parity: the same plans through an N-host
         ``HostGroupExecutor`` (one launch per host) must match too,
         and every host's executor must report megascan jobs — proof
         the route engaged rather than silently falling back.
      3. the roofline claim: per-shard (launches = n_shards) vs
         megascan (launches = 1) records through
         ``benchmarks.roofline.analyze_megascan`` — the megascan's
         dispatch share must drop, and its measured best-of-3 wall on
         the full-fleet plan must beat the per-shard route's.

    Returns the record, including ``roofline_records`` (rendered by
    ``python -m benchmarks.roofline --serve BENCH_serve.json``)."""
    from benchmarks.roofline import analyze_megascan
    from repro.kernels.megascan import MegascanSpec
    from repro.runtime import HostGroupExecutor, PlacementMap
    from repro.runtime.executor import ShardTaskExecutor

    rng = np.random.default_rng(23)
    n_shards = corpus.n_shards
    b = max(4, min(12, batch_size))
    words = pick_query_words(corpus, 3 * b, rng)
    triples = [[int(words[(3 * i + j) % len(words)]) for j in range(3)]
               for i in range(b)]
    vecs = index.query_vectors(triples)
    plans = []
    for i in range(b):
        if i % 4 == 0:
            plans.append([int(rng.integers(n_shards))])
        elif i % 4 == 1:
            sub = rng.choice(n_shards, size=max(2, n_shards // 2),
                             replace=False)
            plans.append(sorted(int(s) for s in sub))
        else:
            plans.append(list(range(n_shards)))

    ex = ShardTaskExecutor(workers=workers)
    sum_spec = MegascanSpec(index, vecs)
    sum_fns = sum_spec.scan_fns()
    ranked_fns = MegascanSpec(index, vecs, ranked_k=10).scan_fns()
    parity = {}
    for label, fns in (("sum", sum_fns), ("ranked", ranked_fns)):
        mega = ex.map_shard_batch(corpus, plans, fns, megakernel=True)
        per = ex.map_shard_batch(corpus, plans, fns, megakernel=False)
        parity[label] = all(
            _mega_scan_equal(m, p) for m, p in zip(mega, per))
        if not parity[label]:
            raise RuntimeError(
                f"megascan {label}-mode group scan does not match the "
                f"per-shard fused path bit-for-bit on ragged plans")

    host_launches = None
    if n_hosts >= 2:
        hg = HostGroupExecutor(
            PlacementMap.blocked(n_shards, n_hosts, n_replicas=1),
            workers_per_host=max(1, workers // n_hosts))
        hmega = hg.map_shard_batch(corpus, plans, sum_fns)
        per = ex.map_shard_batch(corpus, plans, sum_fns, megakernel=False)
        if not all(_mega_scan_equal(m, p) for m, p in zip(hmega, per)):
            raise RuntimeError(
                "megascan host-group gather does not match the "
                "per-shard fused path bit-for-bit")
        host_launches = {h: hx.stats["megascan_jobs"]
                         for h, hx in hg.hosts.items()}
        if not all(v > 0 for v in host_launches.values()):
            raise RuntimeError(
                f"megakernel route did not engage on every host: "
                f"{host_launches}")
        hg.close()

    full = [list(range(n_shards))] * b

    def best_of(megakernel):
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            ex.map_shard_batch(corpus, full, sum_fns,
                               megakernel=megakernel)
            dt = time.perf_counter() - t0
            best = dt if best is None or dt < best else best
        return best

    ex.map_shard_batch(corpus, full, sum_fns, megakernel=True)  # warm
    t_mega = best_of(True)
    rec_mega = dict(sum_spec.last_record, name="megascan_one_launch",
                    measured_wall_s=t_mega)
    t_per = best_of(False)
    # same payload, same flops, n_shards launches, no cross-shard
    # prefetch — slightly flatters the per-shard route (it actually
    # repeats the query projection per launch), which only makes the
    # dispatch-share gate harder to pass
    rec_per = dict(rec_mega, name="megascan_per_shard",
                   launches=n_shards, double_buffer=False,
                   measured_wall_s=t_per, wall_s=t_per)
    # the modeled dispatch share divides by the chip's published
    # peaks; off the chip there are none, so it is not measured there
    records = [rec_per, rec_mega] if on_tpu() else []
    rows = [analyze_megascan(r) for r in records]
    if rows and rows[1]["dispatch_share"] >= rows[0]["dispatch_share"]:
        raise RuntimeError(
            f"megascan dispatch share {rows[1]['dispatch_share']:.3f} "
            f"did not drop below the per-shard route's "
            f"{rows[0]['dispatch_share']:.3f}")
    if t_mega >= t_per:
        raise RuntimeError(
            f"megascan one-launch wall {t_mega:.4f}s is not below the "
            f"per-shard route's {t_per:.4f}s on the full-fleet plan")
    ex.close()
    return dict(
        parity=parity,
        host_group_parity=n_hosts >= 2,
        host_megascan_jobs=host_launches,
        shards=n_shards, queries=b,
        launches=dict(mega=1, per_shard=n_shards),
        measured=dict(mega_s=t_mega, per_shard_s=t_per,
                      win=t_per / t_mega),
        dispatch_share=(dict(mega=rows[1]["dispatch_share"],
                             per_shard=rows[0]["dispatch_share"])
                        if rows else "not measured"),
        dominant=(dict(mega=rows[1]["dominant"],
                       per_shard=rows[0]["dominant"])
                  if rows else "not measured"),
        spec_stats=dict(sum_spec.stats),
        roofline_records=records,
    )


def _placement_report(corpus, index, queries, rate, executor, n_hosts,
                      workers, batch_size) -> dict:
    """The simulated-topology record: parity + residency verification
    (one untimed pass with fresh executors so the scan accounting is
    exact) and a per-host stats snapshot.  Raises on any violation —
    this runs under the CI smoke gate."""
    from repro.core.queries import QueryBatch
    from repro.runtime import HostGroupExecutor, PlacementMap
    placement = PlacementMap.blocked(corpus.n_shards, n_hosts, n_replicas=1)
    hosts = HostGroupExecutor(placement,
                              workers_per_host=max(1, workers // n_hosts))
    engine = QueryBatch(corpus, index, executor=hosts)
    parity = {"count": True, "bool": True, "ranked": True}
    expected_scans = np.zeros(n_hosts, np.int64)
    for i in range(0, len(queries), batch_size):
        chunk = queries[i:i + batch_size]
        seed = 1000 + i
        got = engine.execute(chunk, rate, rng=np.random.default_rng(seed))
        want = QueryBatch(corpus, index, executor=executor).execute(
            chunk, rate, rng=np.random.default_rng(seed))
        for kind, ok in _gather_parity(chunk, got, want).items():
            parity[kind] &= ok
        for h, c in hosts.residency_split(engine.last_plan).items():
            expected_scans[h] += c
    observed = np.asarray(hosts.stats["scans_per_host"], np.int64)
    record = dict(
        hosts=n_hosts, policy="blocked", n_replicas=1,
        scans_per_host=observed.tolist(),
        expected_scans_per_host=expected_scans.tolist(),
        residency_match=bool((observed == expected_scans).all()),
        parity={"count": parity["count"], "bool": parity["bool"],
                "ranked": parity["ranked"]},
        host_stats={k: v for k, v in hosts.stats.items()
                    if k != "scans_per_host"},
    )
    hosts.close()
    if not record["residency_match"]:
        raise RuntimeError(
            f"placement residency violated: per-host scans {observed} "
            f"!= union-plan split {expected_scans}")
    if not all(parity.values()):
        raise RuntimeError(f"cross-host gather parity violated: {parity}")
    return record


def _balance_report(corpus, index, queries, rate, executor, n_hosts,
                    replicas, workers, batch_size) -> dict:
    """The hot-host record: one untimed pass each through the
    primary-only and the balanced split, both with host 0 degraded by
    ``_hot_host_hook``, against the single-executor reference.  Hard
    checks (this runs under the CI smoke gate): balanced results must
    be bit-for-bit the single-executor results, balanced and
    primary-only gathers must match each other, and the balanced split
    must reduce the mean per-job makespan."""
    from repro.core.queries import QueryBatch
    from repro.runtime import HostGroupExecutor, PlacementMap
    wph = max(1, workers // n_hosts)

    def run_arm(balanced):
        pm = PlacementMap.blocked(corpus.n_shards, n_hosts,
                                  n_replicas=replicas)
        hg = HostGroupExecutor(pm, workers_per_host=wph, balanced=balanced,
                               host_fault_hook=_hot_host_hook)
        engine = QueryBatch(corpus, index, executor=hg)
        # warm pass: thread pools and, for the balanced arm, the load
        # model's first look at the hot host (the seeded count-balanced
        # split runs once; measured batches run the learned split)
        engine.execute(queries[:batch_size], rate,
                       rng=np.random.default_rng(99))
        results, makespans = [], []
        for i in range(0, len(queries), batch_size):
            got = engine.execute(queries[i:i + batch_size], rate,
                                 rng=np.random.default_rng(2000 + i))
            results.extend(got)
            makespans.append(max(
                hg.last_job["per_host_wall_s"].values(), default=0.0))
        audit, stats = engine.last_audit, dict(hg.stats)
        stats.pop("scans_per_host", None)
        hg.close()
        return results, float(np.mean(makespans)), audit, stats

    primary_res, primary_ms, _, _ = run_arm(balanced=False)
    bal_res, bal_ms, audit, bal_stats = run_arm(balanced=True)
    ref = QueryBatch(corpus, index, executor=executor)
    want = []
    for i in range(0, len(queries), batch_size):
        want.extend(ref.execute(queries[i:i + batch_size], rate,
                                rng=np.random.default_rng(2000 + i)))

    parity = _gather_parity(queries, bal_res, want)
    parity_vs_primary = _gather_parity(queries, bal_res, primary_res)
    record = dict(
        hosts=n_hosts, policy="blocked", n_replicas=replicas,
        hot_host=0, hot_delay_ms_per_shard=HOT_HOST_DELAY_S * 1e3,
        primary_mean_makespan_ms=primary_ms * 1e3,
        balanced_mean_makespan_ms=bal_ms * 1e3,
        makespan_reduction=primary_ms / max(bal_ms, 1e-12),
        shed_shards=bal_stats.get("shed_shards", 0),
        last_audit=audit,
        parity=parity, parity_vs_primary=parity_vs_primary,
        host_stats=bal_stats,
    )
    if not all(parity.values()):
        raise RuntimeError(
            f"balanced gather diverged from the single executor: {parity}")
    if not all(parity_vs_primary.values()):
        raise RuntimeError(
            f"balanced gather diverged from the primary-only split: "
            f"{parity_vs_primary}")
    if bal_ms >= primary_ms:
        raise RuntimeError(
            f"balanced split did not reduce the hot-host makespan: "
            f"balanced {bal_ms * 1e3:.2f} ms >= primary "
            f"{primary_ms * 1e3:.2f} ms")
    return record


def _budgeted_queries(queries, floor_rate=0.1):
    """The same mixed workload with per-query SLOs attached: counts ask
    for a relative-error budget (the closed-form Eq-2 inversion), bools
    a looser one (bootstrap CI width), ranked a latency budget with an
    error cap (best accuracy that fits ~50 ms p99).  ``floor_rate`` is
    every query's graceful-degradation floor."""
    import dataclasses as _dc

    from repro.runtime import QueryBudget
    out = []
    for q in queries:
        if q.kind == "count":
            b = QueryBudget(max_rel_error=0.5, floor_rate=floor_rate)
        elif q.kind == "bool":
            b = QueryBudget(max_rel_error=0.6, floor_rate=floor_rate)
        else:
            b = QueryBudget(max_rel_error=0.6, max_latency_s=0.05,
                            floor_rate=floor_rate)
        out.append(_dc.replace(q, budget=b))
    return out


def _count_err_stats(queries, results, truths):
    """(p90 relative error, CI-coverage fraction) of the served count
    queries in ``results`` (``None`` slots are shed) against the exact
    full-scan ``truths``."""
    errs, covered, total = [], 0, 0
    for q, res, truth in zip(queries, results, truths):
        if q.kind != "count" or res is None:
            continue
        total += 1
        if res.estimate.covers(truth):
            covered += 1
        if truth:
            errs.append(abs(res.estimate.value - truth) / truth)
    p90 = float(np.percentile(errs, 90)) if errs else 0.0
    return p90, (covered / total if total else 1.0), total


def _budget_report(corpus, index, queries, rate, executor, n_hosts,
                   workers, batch_size) -> dict:
    """The error-budgeted-serving record — three hard gates (this runs
    under the CI smoke job):

      1. *Parity*: a planner-bearing engine serving UNBUDGETED queries
         must be bit-for-bit the plain engine, at the nominal rate and
         at the precise rate-1.0 fast path.
      2. *Calibration*: with budgets attached, the count queries' 95%
         CIs must cover the exact full-scan answer for >= 90% of
         queries — measured on a deterministic untimed pass (pinned
         rng, pressure 0 and pressure 1), not inside the
         timing-dependent overload arm.
      3. *Degrade-before-shed*: under ~3x-capacity Poisson arrivals on
         a 2-host topology with a hot host, the budget-aware window
         (degradation ladder on) must shed strictly fewer queries than
         the PR 3-style static-backpressure baseline under the same
         arrival schedule and queue bound (and the baseline must
         actually shed, or the arm failed to overload).
    """
    from repro.core.queries import QueryBatch
    from repro.runtime import (HostGroupExecutor, PlacementMap, RatePlanner,
                               WindowController)
    plain = QueryBatch(corpus, index, executor=executor)
    budgeted = _budgeted_queries(queries)

    # -- gate 1: unbudgeted parity through the planner ----------------
    planner_engine = QueryBatch(corpus, index, executor=executor,
                                planner=RatePlanner(corpus.n_shards),
                                ci=True)
    parity = {}
    for label, r in (("nominal", rate), ("precise", 1.0)):
        got = planner_engine.execute(queries, r,
                                     rng=np.random.default_rng(31))
        want = plain.execute(queries, r, rng=np.random.default_rng(31))
        parity[label] = _gather_parity(queries, got, want)
        if not all(parity[label].values()):
            raise RuntimeError(
                f"planner engine diverged from the plain engine on "
                f"unbudgeted queries at {label} rate: {parity[label]}")

    # -- gate 2: count-CI coverage, deterministic pass ----------------
    truths = [res.estimate.value if q.kind == "count" else None
              for q, res in zip(queries, plain.execute(
                  queries, 1.0, rng=np.random.default_rng(32)))]
    # warm the planner's error curves off served unbudgeted batches so
    # the budgeted pass plans from fitted dispersion, not the
    # pessimistic cold seed (which would plan a census and make the
    # coverage check vacuous)
    for s in (33, 34):
        planner_engine.execute(queries, rate, rng=np.random.default_rng(s))
    coverage = {}
    audits = {}
    for label, pressure, seeds in (("planned", 0.0, (40, 41)),
                                   ("degraded", 1.0, (42, 43))):
        res_all, q_all, t_all = [], [], []
        for s in seeds:
            res_all.extend(planner_engine.execute(
                budgeted, rate, rng=np.random.default_rng(s),
                pressure=pressure))
            q_all.extend(budgeted)
            t_all.extend(truths)
        p90, cov, n_counts = _count_err_stats(q_all, res_all, t_all)
        coverage[label] = dict(ci_coverage=cov, p90_rel_err=p90,
                               n_count_queries=n_counts)
        audits[label] = planner_engine.last_budget
    for label in ("planned", "degraded"):
        if coverage[label]["ci_coverage"] < 0.9:
            raise RuntimeError(
                f"count 95% CIs cover the exact answer for only "
                f"{coverage[label]['ci_coverage']:.0%} of queries on the "
                f"{label} pass (floor 90%)")

    # -- gate 3: overload — static shedding vs degrade-first ----------
    wph = max(1, workers // n_hosts)

    def hot_exec():
        return HostGroupExecutor(
            PlacementMap.blocked(corpus.n_shards, n_hosts, n_replicas=1),
            workers_per_host=wph, host_fault_hook=_hot_host_hook)

    # capacity probe at the static arm's operating point (hot host
    # included): one warmed batch through a plain engine
    probe_exec = hot_exec()
    probe_engine = QueryBatch(corpus, index, executor=probe_exec)
    probe = budgeted[:batch_size]
    probe_engine.execute(probe, rate, rng=np.random.default_rng(50))
    t0 = time.perf_counter()
    probe_engine.execute(probe, rate, rng=np.random.default_rng(51))
    capacity_qps = len(probe) / (time.perf_counter() - t0)
    probe_exec.close()
    offered = 3.0 * capacity_qps
    overload_queries = (budgeted * ((10 * batch_size) // len(budgeted) + 1)
                        )[:10 * batch_size]

    arms = {}
    for mode, is_budget in (("static", False), ("budget", True)):
        ex = hot_exec()
        sojourns, served_qps, stats, mean_batch, extras = _run_paced_window(
            corpus, index, overload_queries, rate, ex, seed=60,
            arrival_qps=offered, adaptive=is_budget,
            static_delay_s=0.002, static_batch=batch_size,
            max_batch_bound=8 * batch_size, max_pending=4 * batch_size,
            budget=is_budget)
        ex.close()
        p90, cov, n_counts = _count_err_stats(
            overload_queries, extras["results"],
            (truths * ((10 * batch_size) // len(truths) + 1)
             )[:10 * batch_size])
        arms[mode] = dict(
            offered_qps=offered, served_qps=served_qps,
            shed=extras["shed"], served=extras["served"],
            shed_frac=extras["shed"] / extras["offered"],
            degraded_frac=extras["degraded"] / max(extras["served"], 1),
            escalated=extras["escalated"],
            p99_sojourn_ms=float(np.percentile(sojourns, 99)) * 1e3,
            mean_batch=mean_batch,
            p90_rel_err=p90, ci_coverage=cov,
            mean_retry_after_ms=(float(np.mean(extras["retry_hints"]))
                                 * 1e3 if extras["retry_hints"] else None),
            last_budget=extras["last_budget"])
    if arms["static"]["shed"] == 0:
        raise RuntimeError(
            "overload arm failed to overload: the static-backpressure "
            f"baseline shed nothing at {offered:.0f} q/s offered")
    if arms["budget"]["shed"] >= arms["static"]["shed"]:
        raise RuntimeError(
            f"budget-aware serving did not shed strictly fewer than the "
            f"static baseline: {arms['budget']['shed']} >= "
            f"{arms['static']['shed']}")

    return dict(
        hosts=n_hosts, hot_host=0,
        hot_delay_ms_per_shard=HOT_HOST_DELAY_S * 1e3,
        capacity_qps=capacity_qps,
        parity=parity, coverage=coverage,
        planned_audit=audits["planned"], degraded_audit=audits["degraded"],
        overload=arms)


def _chaos_report(corpus, index, queries, rate, executor, n_hosts,
                  workers, batch_size) -> dict:
    """The elastic-fleet chaos record: one scripted, untimed
    kill -> serve-degraded -> join -> recover -> drain scenario driven
    by a seeded ``FaultPlan`` against a ``FleetManager``-managed
    2-host topology, checked batch-by-batch against the
    single-executor reference.  Hard gates (this runs under the CI
    chaos-smoke job):

      1. *Zero lost queries*: every query of every phase returns a
         full-sample result — no partial estimates, no lost shards —
         because one replica survives every scripted failure.
      2. *Gather parity*: every batch, including the one that
         discovers the kill mid-job and requeues on replicas, is
         bit-for-bit the single-executor result (for counts that
         equality covers the CI — so the planned drain provably never
         widens an error bound).
      3. *The kill landed*: the scripted crash fired and the
         single-survivor phase's makespan degraded >= 1.3x the healthy
         baseline (sleep-dominated, so the ratio is deterministic).
      4. *Recovery*: after a warmed replacement host joins, mean job
         makespan returns to within 1.25x the pre-crash baseline, and
         every shard the joiner owns was streamed to it (``warm_fn``)
         before residency swapped.
      5. *Clean drain*: the planned departure moves every shard to a
         live replica (nothing orphaned) and serving continues.
    """
    from repro.core.queries import QueryBatch
    from repro.runtime import (FaultPlan, FleetManager, HostGroupExecutor,
                               PlacementMap)
    hg = HostGroupExecutor(
        PlacementMap.blocked(corpus.n_shards, n_hosts, n_replicas=1),
        workers_per_host=max(1, workers // n_hosts), allow_partial=True)
    plan = FaultPlan(seed=7)
    for h in range(n_hosts + 1):     # + the replacement host joined below
        plan.slow(h, ms_per_shard=CHAOS_SLOW_MS)
    plan.install(hg)
    streamed = []
    fleet = FleetManager(
        hg, warm_fn=lambda sid, src, dst:
        streamed.append([int(sid), int(src), int(dst)]))
    engine = QueryBatch(corpus, index, executor=hg)
    ref = QueryBatch(corpus, index, executor=executor)
    chunks = [queries[i:i + batch_size]
              for i in range(0, len(queries), batch_size)]
    parity = {"count": True, "bool": True, "ranked": True}
    lost_queries = 0
    job_i = 0
    phase_ms = {}

    def run_phase(name, n_batches):
        nonlocal job_i, lost_queries
        makespans = []
        for _ in range(n_batches):
            chunk = chunks[job_i % len(chunks)]
            seed = 3000 + job_i
            got = engine.execute(chunk, rate,
                                 rng=np.random.default_rng(seed))
            want = ref.execute(chunk, rate,
                               rng=np.random.default_rng(seed))
            for kind, ok in _gather_parity(chunk, got, want).items():
                parity[kind] &= ok
            if engine.last_degraded is not None:
                lost_queries += engine.last_degraded["degraded_queries"]
            makespans.append(max(
                hg.last_job["per_host_wall_s"].values(), default=0.0))
            job_i += 1
        # best-of over the phase's batches, same reason the throughput
        # arms take best-of wall time: the sleeps make the true value
        # deterministic, and a container scheduler stall only ever adds
        phase_ms[name] = float(np.min(makespans)) * 1e3

    engine.execute(chunks[0], rate, rng=np.random.default_rng(2999))  # warm
    run_phase("healthy", 2)
    # the kill: host 1 dies NOW (every group job from here on raises);
    # the next batch discovers it mid-job and requeues on replicas
    plan.crash(1, at_job=int(hg.stats["jobs"]))
    run_phase("kill", 1)
    fleet.crash(1)                  # the failure detector catches up
    run_phase("degraded", 2)
    # replacement host (fresh id — the dead slot stays scripted-dead):
    # shards stream to it via warm_fn, then the generation swaps
    join_ev = fleet.join(n_hosts)
    run_phase("recovered", 2)
    # planned departure of the replacement: metadata-only handoff back
    # to live replicas before it leaves rotation
    drain_ev = fleet.drain(n_hosts)
    run_phase("drained", 1)

    record = dict(
        hosts=n_hosts, n_replicas=1, slow_ms_per_shard=CHAOS_SLOW_MS,
        phase_makespan_ms=phase_ms,
        degradation_ratio=phase_ms["degraded"] / max(phase_ms["healthy"],
                                                     1e-9),
        recovery_ratio=phase_ms["recovered"] / max(phase_ms["healthy"],
                                                   1e-9),
        parity=parity, lost_queries=lost_queries,
        lost_shards=int(hg.stats["lost_shards"]),
        warmed_shards=len(streamed), streamed=streamed,
        join=join_ev, drain=drain_ev,
        fleet=fleet.record(), faults=plan.record(),
    )
    hg.close()
    if lost_queries or record["lost_shards"]:
        raise RuntimeError(
            f"chaos scenario lost work: {lost_queries} degraded queries, "
            f"{record['lost_shards']} lost shards (every scripted failure "
            f"leaves a live replica — nothing may be lost)")
    if not all(parity.values()):
        raise RuntimeError(f"chaos gather parity violated: {parity}")
    if plan.fired["crash"] < 1:
        raise RuntimeError("the scripted kill never fired — the scenario "
                           "did not exercise the requeue path")
    if record["degradation_ratio"] < 1.3:
        raise RuntimeError(
            f"single-survivor makespan did not degrade: "
            f"{phase_ms['degraded']:.1f} ms vs healthy "
            f"{phase_ms['healthy']:.1f} ms — the kill did not land")
    if record["recovery_ratio"] > 1.25:
        raise RuntimeError(
            f"post-join makespan did not recover: {phase_ms['recovered']:.1f}"
            f" ms vs healthy {phase_ms['healthy']:.1f} ms "
            f"(> 1.25x)")
    if not streamed or join_ev["warmed_shards"] != len(streamed):
        raise RuntimeError(
            f"join warm-up mismatch: audit says {join_ev['warmed_shards']} "
            f"warmed, warm_fn saw {len(streamed)}")
    if drain_ev["orphaned_shards"] or not drain_ev["planned"]:
        raise RuntimeError(f"drain was not clean: {drain_ev}")
    return record


def _cache_report(corpus, index, queries, rate, executor, n_hosts,
                  workers, batch_size) -> dict:
    """Semantic-cache correctness record, hard-gated.

    Two scenarios, both run at Hamming radius 0 so every reuse is an
    *exact-signature* hit (the bit-for-bit contract; near-hit
    statistics are property-tested in tests/test_qcache.py, not
    benched):

    1. **Exact-hit parity** (single-host, the shared ``executor``):
       a cold pass through a cache-enabled engine must be bit-for-bit
       the plain engine's results under the same rng seeds (the cache
       may not perturb the miss path), and a warm pass under
       *different* seeds must resolve every distinct query from the
       cache with results bit-for-bit equal to the cold pass (hits
       consume no rng and return the memoized estimates verbatim).

    2. **Generation fencing** (``n_hosts`` group + ``FleetManager``):
       populate the cache at one placement epoch, then ``join`` a
       host (RCU generation swap) and re-serve — ZERO cache hits may
       cross the swap, every entry must drop as ``stale_epoch``, and
       the re-served results must match a plain engine on the same
       post-join topology.  Repopulate, ``drain`` the host, and check
       the same again.  A control re-serve *before* the join proves
       the warm cache would have hit, so the zero is the fence and
       not an accident.

    Any violation raises — these are serving-correctness contracts,
    not performance numbers.
    """
    from repro.core.queries import QueryBatch
    from repro.runtime import FleetManager, HostGroupExecutor, PlacementMap
    from repro.runtime.qcache import (QueryCacheConfig, SemanticQueryCache,
                                      query_key)

    # dedupe the pool: _mixed_queries can recycle words on tiny corpora
    # and a duplicate would hit mid-cold-pass, skewing the counts below
    seen, pool = set(), []
    for q in queries:
        k = query_key(q)
        if k not in seen:
            seen.add(k)
            pool.append(q)

    def cache_cfg():
        return QueryCacheConfig(max_entries=4 * len(pool), ttl_s=3600.0,
                                hamming_radius=0)

    def serve(engine, seed_base):
        out = []
        for i in range(0, len(pool), batch_size):
            out.extend(engine.execute(
                pool[i:i + batch_size], rate,
                rng=np.random.default_rng(seed_base + i)))
        return out

    # --- gate 1: exact-hit parity on the single-host executor --------
    cache = SemanticQueryCache(cache_cfg())
    cached_engine = QueryBatch(corpus, index, executor=executor,
                               cache=cache)
    plain = QueryBatch(corpus, index, executor=executor)
    want = serve(plain, 500)
    got_cold = serve(cached_engine, 500)      # same seeds -> same draws
    cold_parity = _gather_parity(pool, got_cold, want)
    if not all(cold_parity.values()):
        raise RuntimeError(
            f"cache MISS path diverged from the uncached engine under "
            f"identical seeds: {cold_parity} — attaching a cold cache "
            f"must be a no-op")
    if cache.stats["hits"] or cache.stats["near_hits"]:
        raise RuntimeError(
            f"cold pass over {len(pool)} distinct queries reported "
            f"{cache.stats['hits']} hits / {cache.stats['near_hits']} "
            f"near-hits — the pool dedup or the keying is broken")
    got_warm = serve(cached_engine, 900)      # different seeds on purpose
    if cache.stats["hits"] != len(pool):
        raise RuntimeError(
            f"warm pass resolved {cache.stats['hits']}/{len(pool)} "
            f"queries from the cache — exact re-asks must all hit")
    warm_parity = _gather_parity(pool, got_warm, want)
    if not all(warm_parity.values()):
        raise RuntimeError(
            f"exact-hit results differ from the uncached execution: "
            f"{warm_parity} — hits must be bit-for-bit the memoized "
            f"result, rng-independent")
    single_host = dict(pool=len(pool), cold_parity=cold_parity,
                       warm_parity=warm_parity, stats=cache.record())

    # --- gate 2: zero hits across fleet generation swaps -------------
    hg = HostGroupExecutor(
        PlacementMap.blocked(corpus.n_shards, n_hosts, n_replicas=1),
        workers_per_host=max(1, workers // n_hosts))
    fleet = FleetManager(hg, warm_fn=lambda sid, src, dst: None)
    fcache = SemanticQueryCache(cache_cfg())
    feng = QueryBatch(corpus, index, executor=hg, cache=fcache)
    fref = QueryBatch(corpus, index, executor=hg)

    serve(feng, 100)                          # populate at epoch e0
    serve(feng, 140)                          # control: warm cache hits
    control_hits = fcache.stats["hits"]
    if control_hits != len(pool):
        raise RuntimeError(
            f"pre-join control re-serve hit {control_hits}/{len(pool)} "
            f"— the warm cache is not actually warm, the join gate "
            f"below would pass vacuously")

    def swap_and_check(event_name, swap):
        ev = swap()
        hits0 = fcache.stats["hits"]
        stale0 = fcache.stats["stale_epoch"]
        got = serve(feng, 180)                # every entry is now stale
        want = serve(fref, 180)               # same seeds, same topology
        stale_hits = fcache.stats["hits"] - hits0
        staled = fcache.stats["stale_epoch"] - stale0
        if stale_hits:
            raise RuntimeError(
                f"{stale_hits} cache hits served across the {event_name} "
                f"generation swap — stale-epoch entries must never hit")
        if staled < len(pool):
            raise RuntimeError(
                f"only {staled}/{len(pool)} entries dropped as "
                f"stale_epoch across {event_name} — the epoch fence "
                f"is not covering the cache")
        parity = _gather_parity(pool, got, want)
        if not all(parity.values()):
            raise RuntimeError(
                f"post-{event_name} re-serve diverged from the plain "
                f"engine on the same topology: {parity}")
        return dict(event=ev, stale_dropped=staled, parity=parity)

    join_rec = swap_and_check("join", lambda: fleet.join(n_hosts))
    # serve(feng, 180) above repopulated at the post-join epoch; the
    # drain swap must fence those entries just the same
    drain_rec = swap_and_check("drain", lambda: fleet.drain(n_hosts))
    fleet_rec = dict(hosts=n_hosts, control_hits=control_hits,
                     join=join_rec, drain=drain_rec,
                     stats=fcache.record(), fleet=fleet.record())
    hg.close()
    return dict(hamming_radius=0, single_host=single_host,
                fleet=fleet_rec)


def _ingest_report(corpus, index, model, pv_cfg, queries, rate, n_hosts,
                   workers, batch_size) -> dict:
    """Live-ingest correctness record, hard-gated.

    One ``Ingestor.step`` appends ``INGEST_FRACTION`` of the corpus —
    every appended doc opens with a sentinel phrase the base corpus
    cannot contain — while the serving loop keeps executing.  The
    gates (any violation raises):

      (a) **zero loss** — no query sheds, degrades, or loses shards
          across the swap (every result carries ``lost_shards == 0``).
      (b) **old-generation parity** — every batch served while the
          swap races is bit-for-bit EITHER the no-ingest reference
          (same seeds, pre-append world) OR the post-append reference
          (same seeds, appended world built sequentially off to the
          side): the RCU capture never hands a batch a torn world.
      (c) **freshness** — after the swap, a precise count of the
          sentinel phrase observes exactly ``n_new`` more matches
          than before, at error bound 0, and the content generation
          advanced exactly once (placement only if shards spilled).
      (d) **zero pause** — serving p99 with the ingest step racing
          stays within ``INGEST_P99_MAX_RATIO`` of the no-ingest p99
          on the same pool: ``INGEST_GATE_PASSES`` pool passes per
          trial, identical seeds and symmetric warmup on both arms,
          p99 over per-query samples, best-of-3 trials each.

    A cache sub-check re-runs the fence contract on the content axis:
    a warm cache must serve ZERO hits across the step, drop every
    entry as ``stale_epoch``, and re-serve bit-for-bit a plain engine
    on the appended world — the ``attach_corpus``/ingest gap the
    placement-only epoch could not see.
    """
    from repro.core.index import refresh_appended
    from repro.core.queries import BatchQuery, QueryBatch
    from repro.launch.serve_stack import build_serving_stack
    from repro.runtime.qcache import QueryCacheConfig, SemanticQueryCache

    rng = np.random.default_rng(71)
    vocab = corpus.vocab_size
    phrase = (vocab - 2, vocab - 1)
    n_new = int(np.ceil(INGEST_FRACTION * corpus.n_docs))
    # sentinel docs: the phrase once at position 0, body drawn below
    # vocab-2 so no other occurrence can form
    new_docs = [np.concatenate([
        np.asarray(phrase, np.int32),
        rng.integers(0, vocab - 2,
                     size=int(rng.integers(10, 50))).astype(np.int32)])
        for _ in range(n_new)]
    fresh_q = [BatchQuery.count(phrase)]
    chunks = [queries[i:i + batch_size]
              for i in range(0, len(queries), batch_size)]

    def serve(engine):
        return [engine.execute(c, rate, rng=np.random.default_rng(7000 + j))
                for j, c in enumerate(chunks)]

    def batch_equal(j, got, want):
        return all(_result_matches(q, g, w)
                   for q, g, w in zip(chunks[j], got, want))

    def no_loss(rounds):
        return all(r.lost_shards == 0 for got in rounds for r in got)

    def stack_kw(**extra):
        return dict(hosts=n_hosts, workers=workers, **extra)

    # --- reference worlds, computed sequentially ---------------------
    with build_serving_stack(corpus, index, **stack_kw()) as ref:
        want_old = serve(ref.engine)
        c0 = ref.engine.execute(fresh_q, 1.0)[0].estimate.value
    grown, _, affected = corpus.append_documents(new_docs)
    post_index = refresh_appended(index, grown, model, pv_cfg, new_docs,
                                  affected, infer_steps=INGEST_INFER_STEPS)
    with build_serving_stack(grown, post_index, **stack_kw()) as ref:
        want_new = serve(ref.engine)

    # --- gates (a)-(c): the racing swap ------------------------------
    ingest_kw = stack_kw(ingest=True, ingest_model=model,
                         ingest_pv_cfg=pv_cfg,
                         ingest_infer_steps=INGEST_INFER_STEPS)
    with build_serving_stack(corpus, index, **ingest_kw) as stack:
        pre = serve(stack.engine)
        for j, (got, want) in enumerate(zip(pre, want_old)):
            if not batch_equal(j, got, want):
                raise RuntimeError(
                    f"batch {j}: an idle attached Ingestor perturbed "
                    f"serving — pre-swap results must be bit-for-bit "
                    f"the plain stack's")
        started = threading.Event()
        step_rec = {}

        def writer():
            started.wait()
            step_rec.update(stack.ingestor.step(new_docs))

        t = threading.Thread(target=writer)
        t.start()
        during = []
        started.set()
        while t.is_alive() and len(during) < 64:
            during.append(serve(stack.engine))
        t.join()
        after = serve(stack.engine)
        served_during = sum(len(r) for r in during)
        old_batches = new_batches = 0
        for rounds in during:
            if not no_loss(rounds):
                raise RuntimeError("a query lost shards during the "
                                   "ingest swap — gate (a)")
            for j, got in enumerate(rounds):
                if batch_equal(j, got, want_old[j]):
                    old_batches += 1
                elif batch_equal(j, got, want_new[j]):
                    new_batches += 1
                else:
                    raise RuntimeError(
                        f"batch {j} served during the swap matches "
                        f"NEITHER the pre-append nor the post-append "
                        f"reference bit-for-bit — torn world, gate (b)")
        for j, got in enumerate(after):
            if not batch_equal(j, got, want_new[j]):
                raise RuntimeError(
                    f"batch {j} after the swap diverged from the "
                    f"post-append reference — the swap did not land "
                    f"cleanly, gate (b)")
        if not (no_loss(pre) and no_loss(after)):
            raise RuntimeError("shard loss outside the swap window — "
                               "gate (a)")
        fres = stack.engine.execute(fresh_q, 1.0)[0]
        if fres.estimate.value != c0 + n_new:
            raise RuntimeError(
                f"freshness: post-swap sentinel count "
                f"{fres.estimate.value} != {c0} + {n_new} appended — "
                f"new docs are not (all) visible, gate (c)")
        if fres.estimate.error_bound != 0.0:
            raise RuntimeError("freshness count was not a precise "
                               "census — gate (c)")
        gen = stack.generation
        if gen.content != 1:
            raise RuntimeError(
                f"content generation is {gen.content} after exactly "
                f"one swap — must mint exactly once, gate (c)")
        want_placement = 1 if step_rec.get("new_shards", 0) else 0
        if n_hosts >= 2 and gen.placement != want_placement:
            raise RuntimeError(
                f"placement generation {gen.placement} != "
                f"{want_placement} (new_shards="
                f"{step_rec.get('new_shards')}) — gate (c)")
        swap_rec = dict(
            n_new=n_new, step=step_rec, served_during_swap=served_during,
            old_generation_batches=old_batches,
            new_generation_batches=new_batches,
            freshness=dict(before=float(c0), after=float(
                fres.estimate.value)),
            generation=gen.record())

    # --- gate (d): p99 with ingest racing vs without -----------------
    # On a few batches "p99" degenerates to the max batch — pure
    # scheduler noise on a shared box, and the one batch colliding
    # with the step's startup burst.  Both arms therefore serve the
    # pool INGEST_GATE_PASSES times per trial with identical seeds,
    # warm symmetrically (a cold pass-set each, so lazily-built
    # postings/jit caches don't masquerade as ingest contention), and
    # the p99 is over per-query amortized samples — the startup-burst
    # batch is < 1% of the mass, so the statistic reflects the
    # steady-state cost of the racing, GIL-paced writer.
    def p99(lat):
        s = np.concatenate([[t / n] * n for t, n in lat])
        return float(np.percentile(s, 99))

    def pool_passes(stack, seed0):
        lat = []
        for r in range(INGEST_GATE_PASSES):
            lat += _run_batched(corpus, index, queries, rate,
                                stack.executor, seed0 + r, batch_size,
                                engine=stack.engine)
        return lat

    with build_serving_stack(corpus, index, **stack_kw()) as plain:
        pool_passes(plain, 0)  # warm
        base = min(p99(pool_passes(plain, 100 * (1 + t)))
                   for t in range(3))
    feed = np.random.default_rng(83)
    with build_serving_stack(corpus, index, **ingest_kw) as stack:
        pool_passes(stack, 0)  # warm
        stack.ingestor.step([feed.integers(0, vocab - 2, 30)
                             .astype(np.int32)])  # warm inference jit
        active = []
        for t in range(3):
            chunk = [feed.integers(0, vocab - 2, 30).astype(np.int32)
                     for _ in range(INGEST_CHUNK_DOCS)]
            th = threading.Thread(target=stack.ingestor.step,
                                  args=(chunk,))
            th.start()
            active.append(p99(pool_passes(stack, 100 * (1 + t))))
            th.join()
        ratio = min(active) / max(base, 1e-9)
        if ratio > INGEST_P99_MAX_RATIO:
            raise RuntimeError(
                f"serving p99 with ingest active is {ratio:.2f}x the "
                f"no-ingest p99 (> {INGEST_P99_MAX_RATIO}x) — the "
                f"append path is pausing serving, gate (d)")
        latency_rec = dict(no_ingest_p99_ms=base * 1e3,
                           ingest_p99_ms=min(active) * 1e3,
                           ratio=ratio, bound=INGEST_P99_MAX_RATIO,
                           passes=INGEST_GATE_PASSES,
                           ingest_steps=stack.ingestor.stats["steps"])

    # --- cache sub-check: the content-axis fence ---------------------
    cache_kw = dict(ingest_kw, cache=True,
                    cache_config=QueryCacheConfig(
                        max_entries=4 * len(queries), ttl_s=3600.0,
                        hamming_radius=0))
    # dedupe the pool the way the cache record does, so hit counts are
    # exact
    from repro.runtime.qcache import query_key
    seen, pool = set(), []
    for q in queries:
        k = query_key(q)
        if k not in seen:
            seen.add(k)
            pool.append(q)
    pool_chunks = [pool[i:i + batch_size]
                   for i in range(0, len(pool), batch_size)]

    def serve_pool(engine, seed_base):
        out = []
        for j, c in enumerate(pool_chunks):
            out.extend(engine.execute(
                c, rate, rng=np.random.default_rng(seed_base + j)))
        return out

    with build_serving_stack(corpus, index, **cache_kw) as stack:
        serve_pool(stack.engine, 100)             # populate
        serve_pool(stack.engine, 140)             # control: warm hits
        control_hits = stack.cache.stats["hits"]
        if control_hits != len(pool):
            raise RuntimeError(
                f"pre-ingest control re-serve hit {control_hits}/"
                f"{len(pool)} — the fence check below would pass "
                f"vacuously")
        stack.ingestor.step(new_docs)             # content bump
        hits0 = stack.cache.stats["hits"]
        stale0 = stack.cache.stats["stale_epoch"]
        got = serve_pool(stack.engine, 180)
        stale_hits = stack.cache.stats["hits"] - hits0
        staled = stack.cache.stats["stale_epoch"] - stale0
        if stale_hits:
            raise RuntimeError(
                f"{stale_hits} cache hits served across the ingest "
                f"content swap — stale entries must never hit")
        if staled < len(pool):
            raise RuntimeError(
                f"only {staled}/{len(pool)} entries dropped as "
                f"stale_epoch across the ingest swap — the content "
                f"axis is not fencing the cache")
        ref_engine = QueryBatch(stack.corpus, stack.index,
                                executor=stack.executor)
        want = serve_pool(ref_engine, 180)
        parity = _gather_parity(pool, got, want)
        if not all(parity.values()):
            raise RuntimeError(
                f"post-ingest re-serve diverged from a plain engine "
                f"on the appended world: {parity}")
        cache_rec = dict(pool=len(pool), control_hits=control_hits,
                         stale_dropped=staled, parity=parity,
                         stats=stack.cache.record())

    return dict(fraction=INGEST_FRACTION, swap=swap_rec,
                latency=latency_rec, cache_fence=cache_rec)


def run_sweep(corpus, index, queries, rate, executor, batch_size) -> list:
    """Static-vs-adaptive window sojourn across arrival rates.

    Rates are anchored to two measured capacities so the sweep spans
    the same regimes on any machine: the *light* end drives 0.1x the
    single-query service rate (windows should serve singles
    immediately — the static 2 ms deadline is pure added latency
    there; the wide margin matters because paced-serving cost runs
    several times the back-to-back probe estimate), and the mid/heavy
    ends drive 0.5x / 1.5x / 3x the *batched* dispatcher capacity
    (where amortization is what keeps the dispatcher stable).

    Three modes per load level: ``static`` and ``adaptive`` windows
    serving the unbudgeted stream (unbounded queue, as before), and
    ``budget`` — the error-budgeted engine behind an adaptive window
    with a bounded queue, so overload exercises the degrade-then-shed
    ladder.  Every row reports the fraction of queries shed vs served
    degraded and the realized p90 relative error of its count queries
    against exact full-scan answers."""
    from repro.core.queries import QueryBatch
    engine = QueryBatch(corpus, index, executor=executor)
    probe = queries[:batch_size]
    engine.execute(probe, rate, rng=np.random.default_rng(5))  # warm
    t0 = time.perf_counter()
    engine.execute(probe, rate, rng=np.random.default_rng(6))
    capacity_qps = len(probe) / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    for i in range(4):
        engine.execute(queries[i:i + 1], rate, rng=np.random.default_rng(7))
    single_qps = 4 / (time.perf_counter() - t0)
    truths = [res.estimate.value if q.kind == "count" else None
              for q, res in zip(queries, engine.execute(
                  queries, 1.0, rng=np.random.default_rng(8)))]
    # percentile stability: each arm serves ~5 windows' worth of queries
    reps = (5 * batch_size) // len(queries) + 1
    sweep_queries = (queries * reps)[:5 * batch_size]
    budget_queries = (_budgeted_queries(queries) * reps)[:5 * batch_size]
    sweep_truths = (truths * reps)[:5 * batch_size]
    arms = [("light", 0.1 * single_qps), ("mid", 0.5 * capacity_qps),
            ("heavy", 1.5 * capacity_qps), ("overload", 3.0 * capacity_qps)]
    rows = []
    for li, (label, arrival_qps) in enumerate(arms):
        arrival_qps = max(arrival_qps, 1.0)
        for mode in ("static", "adaptive", "budget"):
            is_budget = mode == "budget"
            # best-of-3 on p99, same reason the throughput arms take
            # best-of wall time: one scheduler stall in the shared
            # container lands in somebody's tail
            row = None
            for trial in range(3):
                sojourns, realized, stats, mean_batch, extras = \
                    _run_paced_window(
                        corpus, index,
                        budget_queries if is_budget else sweep_queries,
                        rate, executor,
                        seed=10 + li + 100 * trial, arrival_qps=arrival_qps,
                        adaptive=(mode == "adaptive"),
                        static_delay_s=0.002, static_batch=batch_size,
                        max_batch_bound=4 * batch_size,
                        max_pending=(2 * batch_size if is_budget else None),
                        budget=is_budget)
                p90, _, _ = _count_err_stats(
                    budget_queries if is_budget else sweep_queries,
                    extras["results"], sweep_truths)
                cand = dict(
                    load=label, mode=mode,
                    arrival_qps_target=arrival_qps,
                    served_qps=realized,
                    p50_sojourn_ms=float(np.percentile(sojourns, 50)) * 1e3,
                    p99_sojourn_ms=float(np.percentile(sojourns, 99)) * 1e3,
                    windows=stats["batches"], mean_batch=mean_batch,
                    shed_frac=extras["shed"] / extras["offered"],
                    degraded_frac=(extras["degraded"]
                                   / max(extras["served"], 1)),
                    p90_rel_err=p90)
                if row is None or cand["p99_sojourn_ms"] < row["p99_sojourn_ms"]:
                    row = cand
            rows.append(row)
            csv_row(f"serve_sweep_{mode}_{label}",
                    row["p99_sojourn_ms"] * 1e3,
                    f"p99={row['p99_sojourn_ms']:.2f}ms "
                    f"qps={row['served_qps']:.0f}")
    return rows


def run(n_queries: int = 96, rate: float = 0.15, batch_size: int = 48,
        workers: int = 2, trials: int = 3, out_path: str = None,
        smoke: bool = False, sweep: bool = False, hosts: int = 0,
        replicas: int = 1, chaos: bool = False,
        chaos_only: bool = False, zipf: bool = False,
        ingest: bool = False) -> dict:
    chaos = chaos or chaos_only
    zipf = (zipf or smoke) and not chaos_only
    ingest = (ingest or smoke) and not chaos_only
    if smoke:
        # CI budget: tiny corpus, short PV training.  The arms
        # themselves cost milliseconds next to the setup, so 5 trials
        # buy the bench-regression gate a stable best-of measurement
        # for free.  The smoke run always carries the 2-host simulated
        # topology — its row is floored by the regression gate and its
        # parity/residency checks are hard failures — and the chaos
        # arm (scripted kill/join/drain scenario + the batched_chaos
        # row the gate also floors).
        setup = text_setup(tag="smoke", n_docs=400, vocab=2048, topics=8,
                           dim=24, steps=150, bits=128)
        n_queries, batch_size, trials = 48, 12, 5
        hosts = hosts or 2
        chaos = True
    else:
        setup = text_setup()
    if chaos and hosts < 2:
        hosts = 2
    corpus, index = setup["corpus"], setup["index"]
    # doc-granular variant of the same index: planning scores against
    # every doc and reduces to shards through the fused path — the
    # segment-sum Pallas kernels on TPU; on CPU interpret-mode Pallas
    # would swamp the measurement, so the kernels stay off and the
    # fused route is the shard-sorted np.add.reduceat
    index_doc = dataclasses.replace(
        index, granularity="doc",
        use_kernel=on_tpu()).attach_corpus(corpus)
    from repro.runtime.executor import ShardTaskExecutor
    executor = ShardTaskExecutor(workers=workers)
    rng = np.random.default_rng(11)
    queries = _mixed_queries(corpus, n_queries, rng)

    arms = {} if chaos_only else {
        "per_query_scan": lambda seed: _run_per_query_scan(
            corpus, index, queries, rate, executor, seed),
        "per_query": lambda seed: _run_per_query(
            corpus, index, queries, rate, executor, seed),
        "batched": lambda seed: _run_batched(
            corpus, index, queries, rate, executor, seed, batch_size),
        "batched_fused": lambda seed: _run_batched(
            corpus, index_doc, queries, rate, executor, seed, batch_size),
        "windowed": lambda seed: _run_windowed(
            corpus, index, queries, rate, executor, seed, batch_size),
    }
    if not chaos_only:
        # the error-budgeted engine: per-query SLOs through a
        # RatePlanner, bootstrap CIs on (one engine reused across
        # trials, like the balanced arm, so the warm pass is where the
        # error curves fit and measured trials run the learned plans)
        from repro.core.queries import QueryBatch
        from repro.runtime import RatePlanner
        budget_engine = QueryBatch(corpus, index, executor=executor,
                                   planner=RatePlanner(corpus.n_shards),
                                   ci=True)
        budget_queries = _budgeted_queries(queries)
        arms["batched_budget"] = lambda seed: _run_batched(
            corpus, index, budget_queries, rate, executor, seed, batch_size,
            engine=budget_engine)
        # the one-launch scan arm: chunks prebuilt (spec + payload
        # reused across trials), every query scanning the full fleet —
        # one megakernel launch per chunk vs n_shards tasks per chunk
        # on the per-shard route
        mega_chunks = _mega_chunks(corpus, index_doc, n_queries,
                                   batch_size, np.random.default_rng(29))
        arms["batched_mega"] = lambda seed: _run_mega(
            corpus, mega_chunks, executor, seed)
    arm_n = {}                      # per-arm served-query count override
    zipf_stream = cache_stack = None
    if zipf:
        # the semantic-cache arms: the SAME Zipf-skewed stream (2x the
        # distinct pool, hot head) through the plain batched engine
        # (batched_zipf — repeats pay full price) and through a
        # cache-enabled engine reused across trials (batched_cached —
        # the warm pass populates, measured trials serve mostly exact
        # hits that skip sampling, scanning, and the executor).  Both
        # rows are qps/p50 over the stream length, not n_queries.
        from repro.launch.serve_stack import build_serving_stack
        from repro.runtime.qcache import QueryCacheConfig
        zipf_stream = _zipf_stream(queries, ZIPF_STREAM_FACTOR * n_queries,
                                   ZIPF_SKEW, np.random.default_rng(17))
        arms["batched_zipf"] = lambda seed: _run_batched(
            corpus, index, zipf_stream, rate, executor, seed, batch_size)
        cache_stack = build_serving_stack(
            corpus, index, cache=True, workers=workers,
            cache_config=QueryCacheConfig(max_entries=4 * n_queries,
                                          ttl_s=3600.0))
        arms["batched_cached"] = lambda seed: _run_batched(
            corpus, index, zipf_stream, rate, cache_stack.executor, seed,
            batch_size, engine=cache_stack.engine)
        arm_n["batched_zipf"] = arm_n["batched_cached"] = len(zipf_stream)
    ingest_stack = None
    if ingest:
        # the live-ingest arm: the batched pool served through an
        # ingest-enabled stack while a small Ingestor.step (append +
        # frozen-model inference + RCU swap) races each call from a
        # background thread — the row prices writer contention on the
        # serving hot path and is floored by the regression gate.  The
        # corpus grows a little every call (INGEST_CHUNK_DOCS docs),
        # which is the point: ingest-concurrent serving, not a frozen
        # world.
        from repro.launch.serve_stack import build_serving_stack
        # yield_s=0: the timed row prices RAW writer/reader contention
        # (the default cooperative pacing would make it a sleep
        # benchmark); the hard-gated latency record measures the paced
        # configuration instead.
        ingest_stack = build_serving_stack(
            corpus, index, workers=workers, ingest=True,
            ingest_model=setup["model"], ingest_pv_cfg=setup["pv_cfg"],
            ingest_infer_steps=INGEST_INFER_STEPS, ingest_yield_s=0.0)
        ingest_feed = np.random.default_rng(83)

        def _ingest_arm(seed):
            chunk = [ingest_feed.integers(0, corpus.vocab_size - 2, 30)
                     .astype(np.int32) for _ in range(INGEST_CHUNK_DOCS)]
            th = threading.Thread(target=ingest_stack.ingestor.step,
                                  args=(chunk,))
            th.start()
            lat = _run_batched(corpus, index, queries, rate,
                               ingest_stack.executor, seed, batch_size,
                               engine=ingest_stack.engine)
            th.join()
            return lat

        arms["batched_ingest"] = _ingest_arm
    chaos_exec = chaos_plan = None
    if chaos:
        # the chaos-hardened topology under a steady scripted fault
        # load: every host uniformly slowed (sleep-dominated, so the
        # row is machine-stable) and host 1 mildly flaky, so the row
        # prices the injection seams + the deterministic retry path on
        # the batched hot path.  Floored by the regression gate — it
        # collapses if fault handling grows a serialization point.
        from repro.runtime import (FaultPlan, HostGroupExecutor,
                                   PlacementMap)
        chaos_exec = HostGroupExecutor(
            PlacementMap.blocked(corpus.n_shards, hosts,
                                 n_replicas=max(1, replicas)),
            workers_per_host=max(1, workers // hosts))
        chaos_plan = FaultPlan(seed=11).flaky(1, error_rate=0.05)
        for h in range(hosts):
            chaos_plan.slow(h, ms_per_shard=CHAOS_SLOW_MS)
        chaos_plan.install(chaos_exec)
        arms["batched_chaos"] = lambda seed: _run_batched(
            corpus, index, queries, rate, chaos_exec, seed, batch_size)
    host_exec = lb_exec = None
    if hosts >= 2 and not chaos_only:
        from repro.runtime import HostGroupExecutor, PlacementMap
        # same total worker threads as the single-host arms: the row
        # measures placement overhead, not extra parallelism
        host_exec = HostGroupExecutor(
            PlacementMap.blocked(corpus.n_shards, hosts,
                                 n_replicas=replicas),
            workers_per_host=max(1, workers // hosts))
        arms[f"batched_hosts{hosts}"] = lambda seed: _run_batched(
            corpus, index, queries, rate, host_exec, seed, batch_size)
        if replicas >= 1:
            # the hot-host arm: host 0 degraded, balancer on.  The warm
            # pass (arm(0) below) is where the load model learns the
            # heat; measured trials run the learned, shed split
            lb_exec = HostGroupExecutor(
                PlacementMap.blocked(corpus.n_shards, hosts,
                                     n_replicas=replicas),
                workers_per_host=max(1, workers // hosts),
                balanced=True, host_fault_hook=_hot_host_hook)
            arms[f"batched_lb{hosts}"] = lambda seed: _run_batched(
                corpus, index, queries, rate, lb_exec, seed, batch_size)
        else:
            print("NOTE: --replicas 0 — the balanced hot-host arm needs "
                  "at least one replica to shed onto; skipping it")
    per_query_arms = {"per_query_scan", "per_query", "windowed"}
    report = {}
    for name, arm in arms.items():
        arm(0)  # warm (postings caches, jit, thread pools)
        best, best_lat = None, None
        for t in range(trials):
            t0 = time.perf_counter()
            lat = arm(1 + t)
            dt = time.perf_counter() - t0
            if best is None or dt < best:
                best, best_lat = dt, lat
        if name in per_query_arms:
            p50 = float(np.percentile(
                [t if np.isscalar(t) else t[0] for t in best_lat], 50))
        else:
            p50 = float(np.percentile([t / n for t, n in best_lat], 50))
        n_served = arm_n.get(name, n_queries)
        if name == "windowed":
            # open-loop burst: sojourn includes queue backlog behind the
            # single dispatcher, so label it as such instead of p50_ms
            report[name] = dict(qps=n_served / best,
                                p50_sojourn_ms=p50 * 1e3, wall_s=best,
                                note="saturated open-loop burst; sojourn "
                                     "includes dispatcher queue backlog")
        else:
            report[name] = dict(qps=n_served / best, p50_ms=p50 * 1e3,
                                wall_s=best)
        csv_row(f"serve_{name}", 1e6 * best / n_served,
                f"qps={report[name]['qps']:.1f}")

    if chaos:
        report["chaos"] = _chaos_report(
            corpus, index, queries, rate, executor, hosts, workers,
            batch_size)
        report["chaos"]["timed_row_faults"] = chaos_plan.record()
        chaos_exec.close()
        csv_row(f"serve_chaos_hosts{hosts}", 0.0,
                f"recovery {report['chaos']['recovery_ratio']:.2f}x, "
                f"lost {report['chaos']['lost_queries']}, "
                f"warmed {report['chaos']['warmed_shards']}")

    if zipf:
        report["cache"] = _cache_report(
            corpus, index, queries, rate, executor, max(hosts, 2),
            workers, batch_size)
        cached_p50 = report["batched_cached"]["p50_ms"]
        uncached_p50 = report["batched_zipf"]["p50_ms"]
        report["cache"]["zipf"] = dict(
            skew=ZIPF_SKEW, pool=n_queries, stream=len(zipf_stream),
            uncached_p50_ms=uncached_p50, cached_p50_ms=cached_p50,
            p50_collapse=uncached_p50 / max(cached_p50, 1e-9),
            stats=cache_stack.cache.record())
        cache_stack.close()
        # the latency contract: under skewed traffic the cached arm's
        # p50 must be STRICTLY below the uncached arm on the same
        # stream — a cache that hits but does not win latency is
        # overhead, and a regression here means hits stopped skipping
        # the sampling/scan path
        if cached_p50 >= uncached_p50:
            raise RuntimeError(
                f"cached p50 {cached_p50:.3f} ms >= uncached "
                f"{uncached_p50:.3f} ms on the Zipf stream "
                f"(skew {ZIPF_SKEW}) — exact hits are not bypassing "
                f"execution")
        csv_row("serve_cache", 0.0,
                f"p50 collapse "
                f"{report['cache']['zipf']['p50_collapse']:.1f}x, "
                f"hits {report['cache']['zipf']['stats']['hits']}")

    if ingest:
        report["ingest"] = _ingest_report(
            corpus, index, setup["model"], setup["pv_cfg"], queries,
            rate, hosts, workers, batch_size)
        report["ingest"]["timed_row"] = ingest_stack.ingestor.record()
        ingest_stack.close()
        sw = report["ingest"]["swap"]
        csv_row("serve_ingest", 0.0,
                f"+{sw['n_new']} docs, p99 ratio "
                f"{report['ingest']['latency']['ratio']:.2f}x, "
                f"stale dropped "
                f"{report['ingest']['cache_fence']['stale_dropped']}")

    if hosts >= 2 and not chaos_only:
        report["placement"] = _placement_report(
            corpus, index, queries, rate, executor, hosts, workers,
            batch_size)
        ratio = (report[f"batched_hosts{hosts}"]["qps"]
                 / report["batched"]["qps"])
        report["placement"]["qps_ratio_vs_single_host"] = ratio
        csv_row(f"serve_placement_hosts{hosts}", 0.0,
                f"{ratio:.2f}x of single-host")
        host_exec.close()
        if lb_exec is not None:
            report["balance"] = _balance_report(
                corpus, index, queries, rate, executor, hosts, replicas,
                workers, batch_size)
            csv_row(f"serve_balance_hosts{hosts}", 0.0,
                    f"makespan {report['balance']['makespan_reduction']:.2f}x"
                    f" down, shed {report['balance']['shed_shards']}")
            lb_exec.close()
        report["budget"] = _budget_report(
            corpus, index, queries, rate, executor, hosts, workers,
            batch_size)
        ov = report["budget"]["overload"]
        csv_row(f"serve_budget_hosts{hosts}", 0.0,
                f"shed static {ov['static']['shed']} -> budget "
                f"{ov['budget']['shed']}, CI coverage "
                f"{report['budget']['coverage']['planned']['ci_coverage']:.0%}")

    if sweep:
        report["load_sweep"] = run_sweep(corpus, index, queries, rate,
                                         executor, batch_size)

    if not chaos_only:
        report["megascan"] = _megascan_report(
            corpus, index_doc, hosts, workers, batch_size)
        mg = report["megascan"]
        if isinstance(mg["dispatch_share"], dict):
            shares = (f"dispatch share "
                      f"{mg['dispatch_share']['per_shard']:.2f} -> "
                      f"{mg['dispatch_share']['mega']:.2f}, "
                      f"dominant {mg['dominant']['per_shard']} -> "
                      f"{mg['dominant']['mega']}")
        else:
            shares = f"dispatch share {mg['dispatch_share']}"
        csv_row("serve_megascan", 0.0,
                f"win {mg['measured']['win']:.2f}x over per-shard, {shares}")
        report["speedup_batched_vs_per_query"] = (
            report["per_query"]["wall_s"] / report["batched"]["wall_s"])
        report["speedup_batched_vs_scan"] = (
            report["per_query_scan"]["wall_s"] / report["batched"]["wall_s"])
        report["speedup_fused_vs_per_query"] = (
            report["per_query"]["wall_s"]
            / report["batched_fused"]["wall_s"])
        csv_row("serve_speedup_batched_vs_per_query", 0.0,
                f"{report['speedup_batched_vs_per_query']:.2f}x")
        csv_row("serve_speedup_batched_vs_scan", 0.0,
                f"{report['speedup_batched_vs_scan']:.2f}x")
        csv_row("serve_speedup_fused_vs_per_query", 0.0,
                f"{report['speedup_fused_vs_per_query']:.2f}x")
    report["config"] = dict(n_queries=n_queries, rate=rate,
                            batch_size=batch_size, workers=workers,
                            trials=trials, n_shards=corpus.n_shards,
                            n_docs=corpus.n_docs, smoke=smoke,
                            hosts=hosts, replicas=replicas,
                            chaos=chaos, chaos_only=chaos_only,
                            zipf=zipf, zipf_skew=ZIPF_SKEW,
                            ingest=ingest,
                            executor_stats=dict(executor.stats))
    executor.close()

    out_path = out_path or os.environ.get("BENCH_SERVE_JSON",
                                          "BENCH_serve.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus + 1 trial; finishes in <60 s "
                         "(the CI serving smoke job)")
    ap.add_argument("--sweep", action="store_true",
                    help="add the static-vs-adaptive window load sweep "
                         "(Poisson arrivals at several rates)")
    ap.add_argument("--hosts", type=int, default=0,
                    help="add a simulated N-host placement arm "
                         "(batched_hostsN row + placement parity/"
                         "residency record, plus the balanced hot-host "
                         "batched_lbN row + balance record; --smoke "
                         "defaults to 2)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="ring replicas per shard in the placement arms "
                         "(the balanced hot-host arm needs >= 1)")
    ap.add_argument("--chaos", action="store_true",
                    help="add the elastic-fleet chaos arm: the scripted "
                         "kill/join/drain scenario record (hard-gated) "
                         "plus the batched_chaos throughput row "
                         "(--smoke always includes it)")
    ap.add_argument("--zipf", action="store_true",
                    help="add the semantic-cache arms: batched_zipf / "
                         "batched_cached rows on a Zipf-skewed stream "
                         "plus the hard-gated cache correctness record "
                         "(exact-hit parity, zero stale-generation "
                         "hits; --smoke always includes them)")
    ap.add_argument("--ingest", action="store_true",
                    help="add the live-ingest arm: the batched_ingest "
                         "row (serving with an Ingestor.step racing "
                         "each call) plus the hard-gated ingest record "
                         "(zero loss, torn-world parity, sentinel "
                         "freshness, p99 bound, content-axis cache "
                         "fence; --smoke always includes it)")
    ap.add_argument("--chaos-only", action="store_true",
                    help="run ONLY the chaos arm (the CI chaos-smoke "
                         "job): scenario record + batched_chaos row, "
                         "skipping every other arm")
    ap.add_argument("--out", default=None, help="output json path")
    args = ap.parse_args()
    run(smoke=args.smoke, sweep=args.sweep, hosts=args.hosts,
        replicas=args.replicas, chaos=args.chaos,
        chaos_only=args.chaos_only, zipf=args.zipf,
        ingest=args.ingest, out_path=args.out)
