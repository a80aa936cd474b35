"""The query-side serving runtime, bottom-up:

  ``executor``   — single-host fault-tolerant shard tasks (warm pool,
                   retry, straggler speculation, shared scans)
  ``placement``  — shard -> host residency (``PlacementMap``) and the
                   multi-host executor (``HostGroupExecutor``):
                   per-host shared scans, cross-host gather, replica
                   failover
  ``balance``    — replica-aware load balancing (``HostLoadModel`` +
                   ``plan_split``): per-host EWMA cost model over
                   realized host-group wall times, greedy LPT shedding
                   from hot hosts onto live replicas, hysteresis
                   against flapping
  ``window``     — the batching frontend (``BatchWindow``): stream of
                   queries in, deadline/size-closed batches out
  ``controller`` — queueing-theory window autotuner
                   (``WindowController``) + ``Backpressure`` shedding
                   + the degradation-pressure state machine
  ``budget``     — error/latency budgets (``QueryBudget``) and the
                   SLO-driven rate planner (``RatePlanner``): inverts
                   the paper's variance model / fitted error curves to
                   pick the smallest per-query sampling rate meeting
                   each budget
  ``fleet``      — elastic membership (``FleetManager``): host
                   join/drain/crash as first-class, audited operations
                   over the placement layer
  ``qcache``     — the semantic query cache (``SemanticQueryCache``):
                   per-query plans + full results memoized under the
                   index's own LSH signatures, with TTL / generation /
                   LRU invalidation
  ``generation`` — the single generation authority (``Generation`` +
                   ``GenerationClock``): one typed (placement,
                   content) pair replacing the scattered integer
                   epochs; every placement swap and every content swap
                   in a stack mints through one shared clock
  ``chaos``      — deterministic fault injection (``FaultPlan``): a
                   seeded, scripted scenario DSL compiled onto the
                   executors' injection seams

The multi-host dataflow is placement -> balance -> executor: the
``PlacementMap`` bounds where a shard *may* run (primary + live ring
replicas — residency), the balancer picks where it *should* (cost-aware
split, failover as the infinitely-hot-host special case), and the
per-host ``ShardTaskExecutor`` fleet runs the groups, feeding realized
per-host wall times back into the balancer's cost model.  The gather
above is split-agnostic, so every flavor of split produces bit-for-bit
the single-executor results.

``BatchWindow`` takes either executor flavor behind its engine — a
single-host pool and a placement-split host group expose the same
``map_shard_batch`` surface.

The shared scan has three dispatch shapes.  Per-shard (the default for
arbitrary fns): ``run_shared_scan`` builds one composite task per
shard in the union plan and the executor schedules them across its
pool — retry, speculation, and chaos injection all at shard-task
granularity.  One-launch (the megakernel route): when every fn in the
batch comes from one ``kernels.megascan.MegascanSpec``, the composite
closure carries the spec and a megakernel-enabled executor routes the
WHOLE shard group as a single Pallas launch over the block-aligned
packed payload (``_run_group_scan``) — per-(query, shard) partials
come back in exactly the layout the gather already consumes,
bit-for-bit identical to the per-shard path, so everything above the
executor (placement split, balancing, chaos scripts, cache fencing)
is untouched.  On a ``HostGroupExecutor`` this becomes one launch per
host per job: the residency split happens first, then each host's
``ShardTaskExecutor`` fuses its own group.  Fault seams keep per-shard
granularity (hooks fire for every shard in the group before the
launch) while failure/retry is at-least-once at group width;
``megakernel=False`` on ``map_shard_batch`` pins the per-shard fused
path — the parity reference of ``tests/test_megascan.py``.
Host-side group scan (the ranked and Boolean queries of a
``QueryBatch``): their fns carry the batch's
``core.queries.batch.WordScan``, and the executor runs the shard
group's per-shard tasks first — with the per-shard retry, loss and
hooks of the first shape, and only for what the scan leaves, such as
phrase counts — then one vectorised pass over the corpus-wide postings
for the shards that completed, on the calling thread, one per host on
a ``HostGroupExecutor``.

With a cache attached the serving dataflow per query is cache ->
window -> executor: the engine probes the ``SemanticQueryCache``
*before* planning (an exact LSH-signature hit returns the memoized
result with zero scoring, zero rng draws, zero scans; a near-hit
borrows the cached sampling plan — unbiased for any full-support
distribution, Hansen-Hurwitz — and re-runs only the scan + reduce),
the window keeps cache-served queries out of the controller's batch
cost fit (``observe_batch(..., cached=n)``), and every cached entry is
fenced by the engine's composite ``Generation`` — the placement axis
(fleet swaps) AND the content axis (live ingest, ``attach_corpus``) —
so no entry survives either kind of world change.  Degraded,
pressured, and budgeted answers are never cached — a point-in-time
decision must not replay as full fidelity.  Cookbook:

    from repro.launch import build_serving_stack
    stack = build_serving_stack(corpus, index, cache=True,
                                cache_config=QueryCacheConfig(
                                    max_entries=512, ttl_s=30.0,
                                    hamming_radius=8))
    stack.engine.execute(queries, 0.25)       # misses populate
    stack.engine.execute(queries, 0.25)       # exact hits, no scans
    stack.cache.record()                      # hit/near/miss counters

(or hand-wire: ``QueryBatch(corpus, index, executor=...,
cache=SemanticQueryCache(...))``).  ``tests/test_qcache.py`` holds
the contract: exact hits bit-for-bit equal to uncached execution, and
zero hits across join/drain/crash swaps.

Under overload the controller drives *two actuators*, in order:

  1. **degrade** (accuracy): utilization past the saturation band — or
     the pending queue hitting its bound — ratchets the controller's
     ``pressure`` toward 1.0; the window forwards it to an
     accuracy-elastic engine (``QueryBatch`` + ``RatePlanner``), which
     slides every query's sampling rate from its budget-planned value
     toward its budget floor.  Capacity rises because batch service is
     ~linear in shards scanned; answers stay correct because every
     result carries its error bound at whatever rate was served.
  2. **shed** (availability): only once pressure sits at 1.0 — every
     pending query already at its floor — and the queue still
     stretches past twice its bound does ``submit`` raise
     ``Backpressure`` (now with a ``retry_after_s`` hint from the
     controller's plan).

Both directions are hysteretic (asymmetric enter/exit utilization
thresholds, mirroring ``balance``'s asymmetric band), and every
degradation decision lands in a ``BudgetAudit`` on
``last_job["budget"]`` the way balance decisions land on
``last_job["balance"]``.

Fleet lifecycle (``fleet``) rides the same dataflow.  Membership is a
*generation swap*: ``FleetManager`` builds the next ``PlacementMap``
off-line and installs it with ``set_placement`` — every job captures
the placement reference at job start (RCU-style), so in-flight jobs
finish on their old generation while the next job sees the new one,
and serving never pauses.  The three operations share one
residency-transfer path — a drain is a crash you saw coming:

  ``join``   warm first, serve second: every shard the joiner will own
             streams from its current holder (``warm_fn``), and only
             then does the generation swap; the joiner enters the
             ``HostLoadModel`` at the fleet median
  ``drain``  transfer residency to live replicas, then retire — zero
             queries shed, no CI widened (planned=True in the audit)
  ``crash``  retire first (in-flight jobs discover the loss through
             their fault hooks and requeue on replicas), then the same
             transfer with planned=False; shards with no live replica
             orphan and — under ``allow_partial`` — degrade queries to
             partial-sample estimates with widened CIs instead of
             failing (they revive if the slot rejoins)

Live ingest rides the same RCU discipline on a second axis.  The
lifecycle is ingest -> generation -> fence:

  1. **ingest** — ``launch.serve_stack.Ingestor.step(docs)`` builds
     the appended world off to the side: ``data.store``'s
     copy-on-write corpus append (postings deltas merge into any
     built CSR bit-for-bit with a rebuild), then
     ``core.index.refresh_appended`` (frozen-model PV-DBOW inference
     for the new docs — paced with result-neutral cooperative GIL
     yields, ``ingest_yield_s``, so serving threads never stall
     behind the writer — re-centroid/re-sign only the touched
     shards).
  2. **generation** — the new corpus/index refs publish first; only
     then does the stack's shared ``GenerationClock`` mint
     ``bump_content()``.  Readers capture (generation, refs) in that
     order at batch entry, so the one reachable race stamps a *fresh*
     answer with the *old* generation — immediately fenced, never the
     reverse.  An append that spills new shards extends the
     ``PlacementMap`` in place first (old shards keep their hosts;
     that mints ``bump_placement()`` through the same clock).
  3. **fence** — the next probe under the new generation lazily drops
     every entry stamped with the old one (``stats["stale_epoch"]``);
     in-flight batches finish on the refs they captured.  No lock on
     the read path, no serving pause.

Cookbook:

    stack = build_serving_stack(
        corpus, index, cache=True,
        ingest=True, ingest_model=model, ingest_pv_cfg=pv_cfg,
        ingest_source=my_feed)           # or None: drive step() by hand
    stack.ingestor.step(new_docs)        # append + swap, zero pause
    stack.generation                     # Generation(placement, content)
    stack.ingestor.record()              # steps/docs/swaps counters

Mint and compare generations only through ``runtime.generation``.

Every scenario above is testable without wall-clock races via
``chaos``: a ``FaultPlan`` is a seeded script compiled onto the
executors' hooks, its clock the executor's own job counter.  Cookbook:

    plan = (FaultPlan(seed=7)
            .crash(1, at_job=3)           # host 1 dies at group job 3
            .slow(0, ms_per_shard=5)      # host 0 always degraded
            .flaky(2, error_rate=0.1,
                   jobs=range(4, 8))      # transient faults, jobs 4-7
            .stall(0, s=0.2, jobs=[5]))   # one long pause (deadlines)
    plan.install(host_group)              # or a bare ShardTaskExecutor
    ...
    plan.record()                         # scripted + fired, JSON-ready

Flaky decisions draw from a counter-based stream keyed on
``(seed, host, shard, job, attempt)`` — independent of thread
interleaving, identical across runs and machines; a retried shard
redraws and can deterministically recover.  The executor side holds up
its end with bounded-exponential retry backoff, per-job deadlines
(``job_deadline_s``), graceful partials (``allow_partial``), and a
job-epoch guard that drops zombie completions from abandoned jobs.
``tests/test_chaos.py`` replays kill -> degrade -> join -> recover ->
drain against all of this and checks zero lost queries and bit-for-bit
gather parity; ``tests/test_fleet.py`` checks the warm-before-serve
join and the clean drain.
"""
from repro.runtime.balance import (  # noqa: F401
    BalanceConfig,
    HostLoadModel,
    plan_split,
)
from repro.runtime.budget import (  # noqa: F401
    BudgetAudit,
    PlannerConfig,
    QueryBudget,
    RatePlanner,
)
from repro.runtime.controller import (  # noqa: F401
    Backpressure,
    ControllerConfig,
    WindowController,
    WindowPlan,
)
from repro.runtime.chaos import FaultPlan  # noqa: F401
from repro.runtime.executor import ShardTaskExecutor  # noqa: F401
from repro.runtime.fleet import FleetManager  # noqa: F401
from repro.runtime.generation import (  # noqa: F401
    Generation,
    GenerationClock,
)
from repro.runtime.placement import (  # noqa: F401
    HostFailure,
    HostGroupExecutor,
    PlacementMap,
)
from repro.runtime.qcache import (  # noqa: F401
    QueryCacheConfig,
    SemanticQueryCache,
)
from repro.runtime.window import BatchWindow  # noqa: F401
