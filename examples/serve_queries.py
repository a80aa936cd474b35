"""End-to-end driver (the paper's kind: approximate query serving).

Builds the offline index once, then serves a stream of mixed queries —
aggregation, Boolean, ranked — through the *warm adaptive serving
runtime*: queries arrive one by one at a ``BatchWindow`` frontend
driven by the queueing-theory ``WindowController`` (each window opens
with the deadline/size pair currently estimated to minimize p99
sojourn; ``--static`` pins the fixed pair instead), with a bounded
pending queue that sheds via ``Backpressure`` if the dispatcher
saturates; each closed window runs through the batched execution
engine (``QueryBatch``) — one batched scoring pass, per-query pps
sampling, one shared scan over the union of sampled shards — on a
fault-tolerant executor whose thread pool stays warm across batches
(with injected worker faults surviving via retries).  Accuracy is
reported against precise answers computed with a rate-1.0 batch —
itself a single shared scan over all shards.

``--hosts N`` serves through a simulated N-host topology instead: a
blocked ``PlacementMap`` (``--replicas R`` ring replicas per shard)
assigns shard residency, and every window's shared scan splits across
per-host executors with a cross-host gather (the injected shard fault
then lands on whichever host owns the shard and is retried there;
per-host scan counts print at the end).  The replica-aware balancer is
on by default (``--no-balance`` pins the primary-only residency
split): per-host realized wall times feed a load model that sheds
shard groups from hot hosts onto their live replicas.
``--hot-host-ms M`` makes host 0 a straggler (M ms per resident shard
before each of its scans) so the shed is visible — the end-of-run
balance line shows estimated vs realized makespan and how many scans
moved.

``--budget-err E`` (and/or ``--budget-latency-ms L``) switches to
*error-budgeted serving*: every query carries a ``QueryBudget``
(relative error <= E at 95% confidence; p99 sojourn <= L ms;
degradation floor ``--budget-floor``), a ``RatePlanner`` wired to the
window controller inverts the paper's variance model to pick each
query's own sampling rate, and results come back with confidence
intervals (``ci=True``).  The precise reference pass always runs
through a plain engine so the accuracy lines compare against exact
answers.  Shed submits honor the ``Backpressure.retry_after_s`` hint
(back off one serving cycle instead of hot-retrying), and the
end-of-run budget line prints the planner's audit: planned vs realized
rates, degradation pressure, CI coverage of the exact counts.

``--cache`` attaches the semantic query cache (LSH-signature keyed,
``runtime/qcache``) and serves the stream *twice*: the first pass
populates (all misses, bit-for-bit the uncached results), the replay
resolves as exact hits with zero scoring/sampling/scan — the p50
collapse prints at the end alongside the hit/miss counters.

The whole stack — executor topology, planner, cache, controller,
window, fleet — is assembled through the one-call serving facade
(``repro.launch.serve_stack.build_serving_stack``); the flags below
are a thin argparse skin over its ``ServeConfig``.

    PYTHONPATH=src python examples/serve_queries.py [--queries 48]
        [--hosts 2] [--replicas 1] [--hot-host-ms 2] [--no-balance]
        [--budget-err 0.5] [--budget-latency-ms 50] [--cache]
"""
import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=48)
    ap.add_argument("--rate", type=float, default=0.25)
    ap.add_argument("--batch", type=int, default=12,
                    help="max queries per served window")
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="batch window deadline (ms)")
    ap.add_argument("--arrival-us", type=float, default=100.0,
                    help="mean inter-arrival gap of the synthetic "
                         "query stream (microseconds)")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--hosts", type=int, default=0,
                    help="serve through a simulated N-host placement "
                         "(locality-split scans + cross-host gather)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="ring replicas per shard in the placement "
                         "(shed targets for the balancer and failover)")
    ap.add_argument("--no-balance", action="store_true",
                    help="pin the primary-only residency split instead "
                         "of the replica-aware load balancer")
    ap.add_argument("--hot-host-ms", type=float, default=0.0,
                    help="degrade host 0 by this many ms per resident "
                         "shard before each scan (makes the balancer's "
                         "shed visible)")
    ap.add_argument("--static", action="store_true",
                    help="pin the fixed (deadline, batch) pair instead "
                         "of the adaptive window controller")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="pending-queue bound; submits shed with "
                         "Backpressure beyond it (default 8x batch)")
    ap.add_argument("--budget-err", type=float, default=None,
                    help="per-query error budget: max relative error "
                         "at 95%% confidence (e.g. 0.5); attaches a "
                         "RatePlanner and serves with CIs")
    ap.add_argument("--budget-latency-ms", type=float, default=None,
                    help="per-query latency budget: max estimated p99 "
                         "sojourn (ms); caps the planned rate")
    ap.add_argument("--budget-floor", type=float, default=0.1,
                    help="degradation floor rate — overload may "
                         "squeeze a budgeted query down to this rate, "
                         "never below")
    ap.add_argument("--cache", action="store_true",
                    help="attach the LSH-signature semantic query "
                         "cache and replay the stream once to show "
                         "the exact-hit p50 collapse")
    args = ap.parse_args()
    budget_on = (args.budget_err is not None
                 or args.budget_latency_ms is not None)

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.core.allocation import allocate_corpus
    from repro.core.index import build_index
    from repro.core.lsh import LSHConfig
    from repro.core.pv_dbow import PVDBOWConfig, train_pv_dbow
    from repro.core.queries import (BatchQuery, QueryBatch, parse_boolean,
                                    precision_at_k, recall)
    from repro.data.corpus import SyntheticCorpusConfig, generate_text_corpus
    from repro.data.store import ShardedCorpus
    from repro.launch.serve_stack import ServeConfig, build_serving_stack
    from repro.runtime import Backpressure, ControllerConfig, QueryBudget

    print("== offline index build ==")
    ccfg = SyntheticCorpusConfig(n_docs=2400, vocab_size=4096, n_topics=16)
    docs, _ = generate_text_corpus(ccfg)
    corpus = ShardedCorpus.from_documents(docs, ccfg.vocab_size,
                                          shard_tokens=4096)
    pcfg = PVDBOWConfig(dim=48, steps=1200, batch_pairs=4096, lr=0.01)
    model = train_pv_dbow(corpus, pcfg)
    pre = build_index(corpus, model, LSHConfig(bits=128), use_lsh=False,
                      temperature=pcfg.temperature)
    corpus = allocate_corpus(corpus, pre.doc_vecs)
    index = build_index(corpus, model, LSHConfig(bits=256),
                        temperature=pcfg.temperature)
    print(f"   {corpus.n_shards} shards; index {index.nbytes()/1024:.0f} KiB")

    # fault injection: shard 3 fails once per attempt-1; executor retries
    faults = {"injected": 0}

    def fault_hook(sid, attempt):
        if sid == 3 and attempt == 1:
            faults["injected"] += 1
            raise RuntimeError("injected transient fault")

    host_hook = None
    if args.hosts >= 2 and args.hot_host_ms > 0:
        def host_hook(host, shard_ids):
            if host == 0:
                time.sleep(args.hot_host_ms * 1e-3 * len(shard_ids))
    balanced = (args.hosts >= 2 and not args.no_balance
                and args.replicas >= 1)
    max_pending = args.max_pending or 8 * args.batch
    controller_cfg = None
    if not args.static:
        controller_cfg = ControllerConfig(
            min_delay_s=1e-4, max_delay_s=args.window_ms / 1e3,
            min_batch=1, max_batch=args.batch)
    # one call wires executor topology, planner, cache, controller,
    # and window — the facade replaces the old hand-assembly here
    stack = build_serving_stack(corpus, index, ServeConfig(
        rate=args.rate,
        hosts=args.hosts if args.hosts >= 2 else 0,
        replicas=args.replicas, balanced=balanced,
        workers=args.workers, fault_hook=fault_hook,
        host_fault_hook=host_hook, adaptive_workers=True,
        planner=budget_on, ci=budget_on, cache=args.cache,
        window=True, adaptive=not args.static,
        max_batch=args.batch, max_delay_s=args.window_ms / 1e3,
        max_pending=max_pending, controller_config=controller_cfg,
        seed=1))
    executor, engine = stack.executor, stack.engine
    controller, window = stack.controller, stack.window
    if args.hosts >= 2:
        placement = executor.placement
        print(f"   placement: {args.hosts} hosts (blocked, "
              f"{placement.n_replicas} replica); shard residency "
              f"{[len(placement.shards_on(h)) for h in range(args.hosts)]}; "
              f"balancer {'on' if balanced else 'off'}"
              + (f"; host 0 degraded {args.hot_host_ms:.1f} ms/shard"
                 if host_hook else ""))

    rng = np.random.default_rng(0)
    counts = np.bincount(np.concatenate([s.tokens for s in corpus.shards]),
                         minlength=ccfg.vocab_size)
    cand = np.nonzero((counts > 50) & (counts < 1200))[0]

    queries = []
    for i in range(args.queries):
        words = rng.choice(cand, 3, replace=False).astype(int)
        kind = i % 3
        if kind == 0:
            queries.append(BatchQuery.count([int(words[0])]))
        elif kind == 1:
            queries.append(BatchQuery.boolean(parse_boolean(
                [int(words[0]), "or", int(words[1]), "and", int(words[2])])))
        else:
            queries.append(BatchQuery.ranked(words.tolist(), k=10))

    # precise reference answers: one rate-1.0 batch = one full shared
    # scan, always through the plain engine — in budget mode the
    # serving engine carries the planner, and the reference must stay
    # exact regardless of what the planner would do to budgeted queries
    print("== precise reference pass (rate 1.0, one shared scan) ==")
    # always a plain engine: the reference must stay exact (and out of
    # the cache) regardless of planner/cache on the serving engine
    ref_engine = QueryBatch(corpus, index, executor=executor)
    precise = ref_engine.execute(queries, 1.0)

    if budget_on:
        budget = QueryBudget(
            max_rel_error=args.budget_err,
            max_latency_s=(args.budget_latency_ms / 1e3
                           if args.budget_latency_ms is not None else None),
            floor_rate=args.budget_floor)
        queries = [dataclasses.replace(q, budget=budget) for q in queries]
        print(f"   budgets: rel err <= {args.budget_err}"
              + (f", p99 <= {args.budget_latency_ms:.0f} ms"
                 if args.budget_latency_ms is not None else "")
              + f", floor rate {args.budget_floor}; planner attached, "
              f"results carry confidence intervals")
    mode = ("static window" if args.static
            else "adaptive window (p99-sojourn controller)")
    print(f"== serving {args.queries} mixed queries at rate {args.rate} "
          f"through a {args.window_ms:.1f} ms / {args.batch}-query "
          f"{mode}, pending bound {max_pending} ==")
    # with --cache the stream is served twice: pass 1 populates the
    # cache (all misses), pass 2 replays the same queries as exact hits
    stream = list(range(len(queries)))
    if args.cache:
        stream = stream + stream
    arrival_rng = np.random.default_rng(2)
    done_at = {}
    t_submit = {}

    def on_done(i):
        def cb(_fut):
            done_at[i] = time.perf_counter()
        return cb

    t_serve = time.perf_counter()
    futs, shed, retry_hints = [], 0, []
    for i, qi in enumerate(stream):
        q = queries[qi]
        t_submit[i] = time.perf_counter()
        while True:
            try:
                fut = window.submit(q)
                break
            except Backpressure as bp:
                # a real frontend would divert to a replica; the
                # example backs off for the controller's estimated
                # capacity-recovery time (one serving cycle) and
                # retries.  The original t_submit stands — every
                # shed-and-wait penalty is part of the query's sojourn
                shed += 1
                if bp.retry_after_s is not None:
                    retry_hints.append(bp.retry_after_s)
                time.sleep(bp.retry_after_s or args.window_ms / 1e3)
        fut.add_done_callback(on_done(i))
        futs.append(fut)
        if args.arrival_us > 0:
            time.sleep(arrival_rng.exponential(args.arrival_us) / 1e6)
    results = [f.result() for f in futs]
    elapsed = time.perf_counter() - t_serve
    window.close()

    lat = {"agg": [], "bool": [], "ranked": []}
    acc = {"agg": [], "bool": [], "ranked": []}
    kind_of = {"count": "agg", "bool": "bool", "ranked": "ranked"}
    for i, (q, r, ref) in enumerate(zip(queries, results, precise)):
        # pass-1 results only: the replay (if any) repeats the same
        # queries and lands in the cache line below
        k = kind_of[q.kind]
        lat[k].append(done_at[i] - t_submit[i])
        if q.kind == "count":
            if ref.estimate.value:
                acc[k].append(abs(r.estimate.value - ref.estimate.value)
                              / ref.estimate.value)
        elif q.kind == "bool":
            acc[k].append(recall(r.doc_ids, ref.doc_ids))
        else:
            acc[k].append(precision_at_k(r.doc_ids, ref.doc_ids, 10))

    ws = window.stats
    sojourn = np.asarray([done_at[i] - t_submit[i]
                          for i in range(len(stream))])
    print(f"   throughput: {len(stream)/elapsed:8.1f} queries/sec "
          f"({len(stream)} queries in {elapsed:.2f}s)")
    print(f"   sojourn: p50 {np.percentile(sojourn, 50)*1e3:.2f} ms | "
          f"p99 {np.percentile(sojourn, 99)*1e3:.2f} ms")
    if args.cache:
        n = len(queries)
        p50_cold = np.percentile(sojourn[:n], 50) * 1e3
        p50_hot = np.percentile(sojourn[n:], 50) * 1e3
        rec = stack.cache.record()
        print(f"   cache: replay p50 {p50_hot:.2f} ms vs cold "
              f"{p50_cold:.2f} ms ({p50_cold / max(p50_hot, 1e-9):.1f}x); "
              f"{rec['hits']} hits / {rec['near_hits']} near / "
              f"{rec['misses']} misses / {rec['bypassed']} bypassed "
              f"({rec['size']} entries)")
    print(f"   windows: {ws['batches']} "
          f"(by size {ws['closed_by_size']}, "
          f"by deadline {ws['closed_by_deadline']}, "
          f"by flush {ws['closed_by_flush']}); "
          f"shed by backpressure: {shed}"
          + (f" (mean retry-after hint "
             f"{1e3 * sum(retry_hints) / len(retry_hints):.1f} ms)"
             if retry_hints else "")
          + (f"; pressure escalations: {ws['escalated']}, "
             f"served degraded: {ws['degraded']}"
             if ws.get("escalated") or ws.get("degraded") else ""))
    if controller is not None and controller.current_plan is not None:
        plan = controller.current_plan
        scan = controller.scan_fraction
        print(f"   controller: deadline {plan.delay_s*1e3:.2f} ms, "
              f"batch {plan.max_batch}, est p99 {plan.est_p99_s*1e3:.2f} ms, "
              f"utilization {plan.utilization:.2f}, "
              f"arrival rate {plan.arrival_rate:.0f}/s"
              + (f", scan share {scan:.0%}" if scan is not None else ""))
    if budget_on:
        cover, n_counts, rates = 0, 0, []
        for q, r, ref in zip(queries, results, precise):
            rates.append(r.achieved_rate)
            if q.kind == "count":
                n_counts += 1
                cover += int(r.estimate.covers(ref.estimate.value))
        print(f"   budget: count 95% CIs cover the exact answer "
              f"{cover}/{n_counts}; mean achieved rate "
              f"{np.mean(rates):.2f} (nominal {args.rate})")
        audit = window.last_budget
        if audit:
            print(f"   planner audit (last window): pressure "
                  f"{audit['pressure']:.2f}, {audit['degraded']}/"
                  f"{audit['budgeted']} degraded, {audit['at_floor']} at "
                  f"floor; planned rates p50 "
                  f"{np.median(audit['planned_rates']):.2f}, realized rel "
                  f"err p50 {np.median(audit['realized_rel_error']):.2f}")
    if args.hosts >= 2:
        retries = sum(ex.stats["retries"] for ex in executor.hosts.values())
        print(f"   injected faults survived: {faults['injected']} "
              f"(task retries across hosts: {retries}; host failures: "
              f"{executor.stats['host_failures']}; requeued shards: "
              f"{executor.stats['requeued_shards']})")
        print(f"   per-host scans: {executor.stats['scans_per_host']} "
              f"over {executor.stats['jobs']} gather jobs")
        audit = engine.last_audit
        if audit is not None:
            print(f"   balance: split {audit['group_sizes']} vs residency "
                  f"{audit['base_group_sizes']} "
                  f"({'shed ' + str(audit['shed']) if audit['balanced'] else 'held by hysteresis'}; "
                  f"{executor.stats['shed_shards']} scans shed total); "
                  f"last-job makespan est "
                  f"{audit['est_makespan_s'] * 1e3:.2f} ms / realized "
                  f"{audit['realized_makespan_s'] * 1e3:.2f} ms "
                  f"(residency split would est "
                  f"{audit['est_base_makespan_s'] * 1e3:.2f} ms)")
    else:
        print(f"   injected faults survived: {faults['injected']} "
              f"(executor retries: {executor.stats['retries']}; warm pool "
              f"rebuilds: {executor.stats['pool_rebuilds']} across "
              f"{executor.stats['jobs']} jobs)")
    for kind, metric in (("agg", "mean rel err"), ("bool", "mean recall"),
                         ("ranked", "mean P@10")):
        if lat[kind]:
            print(f"   {kind:7s}: p50 sojourn latency "
                  f"{np.percentile(lat[kind], 50)*1e3:7.2f} ms | "
                  f"{metric} {np.mean(acc[kind]):.3f}")
    stack.close()


if __name__ == "__main__":
    main()
