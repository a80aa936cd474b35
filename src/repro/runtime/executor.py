"""Fault-tolerant shard-task executor (the query-side runtime).

This is the Spark-executor analogue for EmApprox query jobs: per-shard
tasks run on a worker pool with

  * retry on failure (transient worker faults) with *bounded
    exponential backoff*: the ``r``-th retry of a shard waits
    ``retry_backoff_s * 2**(r-1)`` (capped at ``retry_backoff_cap_s``)
    before resubmitting, so a flaky dependency is not hammered at
    queue speed,
  * a per-job deadline (``job_deadline_s``): a job that cannot finish
    in time stops retrying and — with ``allow_partial=True`` — returns
    the shards it *did* complete, recording the rest on
    ``last_job["lost_shards"]`` so the query layer can degrade to a
    partial-sample estimate with a widened CI instead of failing the
    whole batch (without ``allow_partial`` the deadline raises
    ``ShardTaskError`` exactly like exhausted retries),
  * straggler mitigation: when the slowest ~tail of tasks exceeds
    ``straggler_factor``x the median completion time, duplicates are
    speculatively launched and the first finisher wins (the classic
    MapReduce backup-task trick),
  * elastic worker count: pool size can change between jobs,
  * a *warm* pool: the thread pool is built lazily on the first job and
    kept alive across jobs (long-lived serving was paying a pool
    construction + teardown per batch), rebuilt only when the target
    worker count changes; ``close()`` (or the context manager) tears it
    down,
  * the *sweep* (on unless ``gil_floor_s=0.0`` or a ``job_deadline_s``
    is set): a job starts on the calling thread, running its shards in
    order with no pool, futures or completion queue — for ~10-100 us
    numpy tasks the pool's hand-offs under the interpreter lock cost
    more than the work — and hands the rest to the pool once its tasks
    prove long (median at least ``gil_floor_s``, checked from the first
    task on, or one past the straggler threshold).
    ``stats["sweep_jobs"]`` counts the jobs that never left the
    calling thread, ``last_job["sweep"]`` marks one.

This executor is the *single-host* layer: it treats every shard it is
handed as locally resident.  Multi-host topologies stack
``runtime/placement.HostGroupExecutor`` on top, with the dataflow
placement -> balance -> executor: a ``PlacementMap`` bounds where each
shard may run (primary residency + live ring replicas), the optional
``runtime/balance`` layer picks where it should (cost-aware shedding
from hot hosts onto replicas, fed by the per-host realized wall times
this layer reports via ``last_job``), and one ``ShardTaskExecutor``
per host runs its group (per-host warm pool, per-host retry and
speculation) before a cross-host gather merges the per-shard results.
Failure injection for tests is via ``fault_hook`` which may raise on
chosen shards (host-granularity injection lives on the placement
layer).

Shared-scan scheduling (``map_shard_batch``): a batch of queries, each
with its own sampled shard plan, is inverted into one task per shard in
the *union* of the plans; visiting a shard evaluates every query that
sampled it in a single pass.  I/O and task overhead scale with the
union size instead of the sum of per-query plan sizes, and retry /
speculation apply to the composite shard task, so a retried shard
re-evaluates all of its queries (same at-least-once semantics as
``map_shards``).  The schedule itself (invert the plans, visit once,
scatter back per query) is ``run_shared_scan`` — one definition shared
by this executor, the placement layer's per-host scans, and the
executor-less inline fallback in ``core/queries/batch.py``, so the
schedules cannot diverge.

Fault injection has two first-class seams, both consumed by the
``runtime/chaos`` FaultPlan compiler: ``fault_hook(shard_id, attempt)``
(the legacy raise-to-fail hook) and ``task_hook(shard_id, attempt,
job)`` — the per-shard-task hook carrying the executor's job index, so
a scripted plan can target "shard tasks during jobs 3..5" without
keeping its own clock.  ``job_hook(job)`` fires once at job start.

Completions are tagged with a *job epoch*: a job abandoned at its
deadline leaves speculative/stalled futures running on the warm pool,
and when those finish late their completion records carry the old
epoch and are dropped (``stats["stale_completions"]``) instead of
polluting a later job's accounting.
"""
from __future__ import annotations

import heapq
import logging
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, Optional, Sequence

import numpy as np

from repro.utils import spans

_log = logging.getLogger(__name__)


class ShardTaskError(RuntimeError):
    pass


def invert_plan(plan: Sequence[Sequence[int]]) -> Dict[int, list]:
    """{shard_id: [query indices]} union of per-query shard plans — the
    shared-scan schedule.  One definition serves the executor's
    ``map_shard_batch``, the placement layer's residency split, and the
    executor-less inline fallback in ``core/queries/batch.py`` so the
    schedules cannot diverge."""
    queries_of: Dict[int, list] = {}
    for qi, shard_ids in enumerate(plan):
        for sid in shard_ids:
            queries_of.setdefault(int(sid), []).append(qi)
    return queries_of


def run_shared_scan(
    mapper: Callable[[Any, Sequence[int], Callable[[Any], Any]],
                     Dict[int, Any]],
    corpus,
    plan: Sequence[Sequence[int]],
    fns: Sequence[Callable[[Any], Any]],
    *,
    megakernel: Optional[bool] = None,
) -> "list[Dict[int, Any]]":
    """The full shared-scan schedule over any ``map_shards``-shaped
    mapper: invert the per-query plans, visit each union shard once
    (evaluating every interested query in that visit), and scatter the
    per-shard composites back into one ``{shard_id: result}`` dict per
    query.  ``ShardTaskExecutor.map_shard_batch`` runs it on the local
    pool; ``HostGroupExecutor.map_shard_batch`` runs it through the
    residency split + cross-host gather — same schedule either way.

    When every fn carries the same ``kernels/megascan`` ``MegascanSpec``
    (built via ``MegascanSpec.scan_fns()``), the composite shard task
    becomes ``spec.run_shard`` — the per-shard *fused* scan, one Pallas
    launch per shard for all interested queries — and, unless
    ``megakernel=False``, the composite is tagged with the spec so a
    spec-aware mapper (a megakernel-enabled ``ShardTaskExecutor``) can
    fuse its whole shard group into ONE launch (``spec.run_group``).
    The gather below is the contract either way: per-(query, shard)
    results scattered into one ``{shard_id: result}`` dict per query,
    bit-for-bit identical across routes.  ``megakernel=True`` asserts
    the fns are fusable (raises otherwise); ``None`` auto-detects;
    ``False`` pins the per-shard fused path (the parity reference and
    the fallback when grouping is disabled).

    Otherwise, when some fns carry a host-side group scan (``fn.group``:
    ``core/queries/batch.WordScan`` on a batch's ranked and Boolean
    queries), the composite is tagged for the executor's group route
    (``_tag_group_scan``): the shards' per-shard tasks run what the
    scan does not answer, then one ``run_group`` call answers the rest
    (``finish_group_scan``).  A mapper that knows no group route calls
    the composite per shard, which runs every fn as before."""
    if len(plan) != len(fns):
        raise ValueError(f"plan/fns length mismatch: "
                         f"{len(plan)} != {len(fns)}")
    queries_of = invert_plan(plan)

    spec = None
    if fns:
        cand = getattr(fns[0], "megascan", None)
        if cand is not None and all(
                getattr(f, "megascan", None) is cand for f in fns):
            spec = cand
    if megakernel is True and spec is None:
        raise ValueError("megakernel=True requires scan fns built from "
                         "one MegascanSpec (MegascanSpec.scan_fns())")

    if spec is not None:
        def shared_scan(shard):
            return spec.run_shard(shard.shard_id,
                                  queries_of[shard.shard_id])
        if megakernel is not False:
            shared_scan.megascan = spec
            shared_scan.queries_of = queries_of
    else:
        def shared_scan(shard):
            return {qi: fns[qi](shard)
                    for qi in queries_of[shard.shard_id]}
        _tag_group_scan(shared_scan, fns, queries_of)

    by_shard = mapper(corpus, sorted(queries_of), shared_scan)
    out: list = [{} for _ in plan]
    with spans.span("emapprox.gather"):
        for sid, per_query in by_shard.items():
            for qi, res in per_query.items():
                out[qi][sid] = res
    return out


def _tag_group_scan(shared_scan, fns, queries_of) -> None:
    """Tag the composite for the group route when some fns carry a
    group scan (``fn.group``, one object with ``covers(shard_id)`` and
    ``run_group(shard_ids, queries_of)``): ``shared_scan.group`` is the
    scan, ``.queries_of`` the (shard, query) pairs it answers, the
    covered shards' tagged queries, and ``.per_shard`` the composite
    task of what is left.  Untagged fns and uncovered shards keep their
    per-shard tasks."""
    scan = next((f.group for f in fns if hasattr(f, "group")), None)
    if scan is None:
        return
    tagged = {qi for qi, f in enumerate(fns)
              if getattr(f, "group", None) is scan}
    grouped: Dict[int, list] = {}
    for sid, qis in queries_of.items():
        mine = (qis if len(tagged) == len(fns)
                else [qi for qi in qis if qi in tagged])
        if mine and scan.covers(sid):
            grouped[sid] = mine
    if not grouped:
        return

    def per_shard(shard):
        skip = grouped.get(shard.shard_id, ())
        return {qi: fns[qi](shard)
                for qi in queries_of[shard.shard_id] if qi not in skip}

    shared_scan.group = scan
    shared_scan.queries_of = grouped
    shared_scan.per_shard = per_shard
    shared_scan.hooks_only = (
        frozenset(grouped) if len(tagged) == len(fns)
        else frozenset(sid for sid, qis in grouped.items()
                       if len(qis) == len(queries_of[sid])))


def finish_group_scan(fn, results: Dict[int, Any]) -> None:
    """The group pass of a group-tagged composite ``fn`` (see
    ``run_shared_scan``), once its per-shard tasks have run: the scan
    answers its pairs on the shards in ``results`` in one
    ``run_group`` call, merged into their per-shard dicts.  A shard
    missing from ``results`` (lost under ``allow_partial``) is left
    out."""
    grouped = fn.queries_of
    done = [sid for sid in results if sid in grouped]
    if done:
        for sid, res in fn.group.run_group(done, grouped).items():
            results[sid].update(res)


class ShardTaskExecutor:
    def __init__(
        self,
        workers: int = 4,
        max_retries: int = 2,
        straggler_factor: float = 3.0,
        min_completed_for_speculation: int = 4,
        fault_hook: Optional[Callable[[int, int], None]] = None,
        min_straggler_s: float = 0.05,
        gil_floor_s: float = 1e-3,
        retry_backoff_s: float = 0.0,
        retry_backoff_cap_s: float = 1.0,
        job_deadline_s: Optional[float] = None,
        allow_partial: bool = False,
        task_hook: Optional[Callable[[int, int, int], None]] = None,
        job_hook: Optional[Callable[[int], None]] = None,
        megakernel: bool = True,
    ):
        self.workers = workers
        # Spec-tagged shared scans (kernels/megascan MegascanSpec) run
        # the whole shard group as ONE Pallas launch instead of one
        # composite task per shard; False pins the per-shard fused
        # path (parity reference / interpret-mode fallback).
        self.megakernel = bool(megakernel)
        self.max_retries = max_retries
        self.straggler_factor = straggler_factor
        self.min_completed = min_completed_for_speculation
        self.fault_hook = fault_hook  # (shard_id, attempt) -> None or raise
        # chaos seams: per-shard-task hook with the executor's job index
        # (slow/flaky injection at task granularity) and a job-start
        # hook (lets a FaultPlan injector advance its clock)
        self.task_hook = task_hook    # (shard_id, attempt, job)
        self.job_hook = job_hook      # (job) at job start
        # attempt k of a failed shard waits backoff * 2^(k-1) (capped)
        # before resubmission; 0.0 keeps the legacy immediate retry
        self.retry_backoff_s = float(retry_backoff_s)
        self.retry_backoff_cap_s = float(retry_backoff_cap_s)
        # a job that cannot finish by its deadline stops retrying; with
        # allow_partial it returns what completed (lost shards recorded
        # on last_job), otherwise it raises like exhausted retries
        self.job_deadline_s = job_deadline_s
        self.allow_partial = bool(allow_partial)
        # Floor on the speculation threshold: when the median task time
        # is below the scheduler's own tick (tasks of ~100 us at batch
        # scale), 3x the median is noise-level and speculation would
        # duplicate healthy tasks — a backup task is only worth
        # launching for work at least as long as a scheduling quantum.
        self.min_straggler_s = min_straggler_s
        self.gil_floor_s = gil_floor_s
        self.stats: Dict[str, int] = {"retries": 0, "speculative": 0,
                                      "jobs": 0, "pool_rebuilds": 0,
                                      "lost_shards": 0,
                                      "stale_completions": 0,
                                      "megascan_jobs": 0,
                                      "sweep_jobs": 0}
        # job epoch: bumped at every job start; completion records are
        # tagged with it so futures abandoned by a deadline-expired job
        # are recognizably stale when they finish late.  The completions
        # queue is instance-level (not job-local) and jobs are
        # serialized on _job_lock, so a zombie future's late completion
        # lands in a *live* loop where the epoch guard can count and
        # drop it instead of vanishing into a dead queue.
        self._job_epoch = 0
        self._job_lock = threading.Lock()
        self._completions: "queue.Queue[tuple]" = queue.Queue()
        # per-job service-time telemetry for the last completed job —
        # the window controller reads this to attribute batch cost to
        # the shared scan (wall_s) vs engine overhead; see
        # runtime/controller.WindowController.observe_batch
        self.last_job: Optional[Dict[str, float]] = None
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pool_size = 0
        self._pool_lock = threading.Lock()

    def resize(self, workers: int) -> None:
        """Elastic scaling between jobs (the warm pool is swapped on the
        next job, not mid-flight)."""
        self.workers = max(1, workers)

    # ------------------------------------------------------------------
    # warm pool management
    # ------------------------------------------------------------------
    def _acquire_pool(self) -> ThreadPoolExecutor:
        """The long-lived worker pool, (re)built only when ``workers``
        changed since it was built (``resize``).  Jobs are serialized on
        ``_job_lock``, so no job is using the pool when it is swapped."""
        with self._pool_lock:
            target = max(1, int(self.workers))
            if self._pool is None or self._pool_size != target:
                if self._pool is not None:
                    self._pool.shutdown(wait=False)
                self._pool = ThreadPoolExecutor(
                    max_workers=target, thread_name_prefix="shard-worker")
                self._pool_size = target
                self.stats["pool_rebuilds"] += 1
            return self._pool

    def close(self) -> None:
        """Tear down the warm pool (idempotent).  Call when no job is
        in flight — shutting down under a running ``map_shards`` fails
        that job's remaining submits."""
        with self._pool_lock:
            pool, self._pool, self._pool_size = self._pool, None, 0
        if pool is not None:
            pool.shutdown(wait=True)

    def __enter__(self) -> "ShardTaskExecutor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def map_shards(
        self,
        corpus,
        shard_ids: Sequence[int],
        fn: Callable[[Any], Any],
    ) -> Dict[int, Any]:
        """Run ``fn(shard)`` for every id; returns {shard_id: result}.

        The job starts as a sweep on the calling thread (``_sweep``) and
        moves to the warm pool only when its tasks are long enough to
        gain from threads; a job with a deadline starts on the pool.
        The pooled completion loop is event-driven: every future
        signals a queue via ``add_done_callback`` and the scheduler
        blocks on that queue, so bookkeeping is O(1) per completion.  (The previous ``wait(..., FIRST_COMPLETED)``
        polling loop re-registered a waiter on every still-pending
        future each iteration — O(tasks) per completion, O(tasks^2) per
        job — which at shared-scan batch sizes cost more than the shard
        work itself.)  Straggler checks run on 50 ms ticks and on each
        completion.

        A ``MegascanSpec``-tagged composite (see ``run_shared_scan``)
        short-circuits the per-shard task fan-out entirely: the whole
        group runs as ONE Pallas launch (``_run_group_scan``) when this
        executor was built with ``megakernel=True``.  A composite tagged
        with a host-side group scan runs its per-shard tasks as above
        and then the scan's one pass (``_run_group_scan`` too).
        """
        spec = getattr(fn, "megascan", None)
        if spec is None or not self.megakernel:
            spec = getattr(fn, "group", None)
        if spec is not None:
            with self._job_lock:
                return self._run_group_scan(corpus, shard_ids, fn, spec)
        # jobs are serialized: the epoch guard on the shared
        # completions queue assumes one live job owns the loop
        with self._job_lock:
            return self._run_job(corpus, shard_ids, fn)

    def _run_job(
        self,
        corpus,
        shard_ids: Sequence[int],
        fn: Callable[[Any], Any],
        hooks_only: frozenset = frozenset(),
    ) -> Dict[int, Any]:
        """One per-shard job: the sweep on the calling thread first
        (``gil_floor_s > 0`` and no ``job_deadline_s``), then the pooled
        loop for whatever the sweep handed off.  ``gil_floor_s=0.0``
        runs the pooled loop alone, and so does a job with a deadline:
        only the pool can abandon a stalled task when the deadline
        expires.

        ``hooks_only``: shards whose task has nothing to run (a group
        scan answers all their queries afterwards).  Where the job
        sweeps, they fire their hooks, under the same retry contract,
        and take ``{}`` without a task call or a timing; elsewhere they
        run ``fn`` like any other shard."""
        ids = [int(s) for s in shard_ids]
        t_job = time.perf_counter()
        deadline = (t_job + self.job_deadline_s
                    if self.job_deadline_s is not None else None)
        self._job_epoch += 1
        job = self.stats["jobs"]
        if self.job_hook is not None:
            self.job_hook(job)
        results: Dict[int, Any] = {}
        attempts: Dict[int, int] = {i: 0 for i in ids}
        durations: list = []
        rest, timed_out = ids, False
        sweeping = self.gil_floor_s > 0.0 and deadline is None
        if sweeping:
            if hooks_only:
                hooked = (self.fault_hook is not None
                          or self.task_hook is not None)
                for sid in ids:
                    if sid in hooks_only and (not hooked or self._attempt(
                            corpus, sid, None, job, attempts)[0]):
                        results[sid] = {}
                rest = [s for s in ids if s not in hooks_only]
            rest = self._sweep(corpus, rest, fn, job, results, attempts,
                               durations)
        swept = sweeping and not rest
        if rest:
            timed_out = self._run_pooled(
                self._acquire_pool(), corpus, ids, rest, fn, job, deadline,
                results, attempts, durations)
        missing = [s for s in ids if s not in results]
        if missing and not self.allow_partial:
            if timed_out:
                raise ShardTaskError(
                    f"job deadline ({self.job_deadline_s}s) expired; "
                    f"shards incomplete: {missing}")
            raise ShardTaskError(f"shards never completed: {missing}")
        self.stats["lost_shards"] += len(missing)
        median_task = float(np.median(durations)) if durations else 0.0
        self.stats["jobs"] += 1
        self.stats["sweep_jobs"] += swept
        self.last_job = {
            "wall_s": time.perf_counter() - t_job,
            "tasks": float(len(ids)),
            "median_task_s": median_task,
            "lost_shards": float(len(missing)),
            "sweep": float(swept),
        }
        return results

    def _sweep(self, corpus, ids: list, fn, job: int,
               results: Dict[int, Any], attempts: Dict[int, int],
               durations: list) -> list:
        """Run the job's shards in order on the calling thread: no pool,
        futures or completion queue, so a ~10-100 us numpy task costs
        its own time instead of ~70 us of hand-offs under the
        interpreter lock.  The per-shard contract is the pooled loop's:
        both hooks on every attempt, retries up to ``max_retries`` with
        the same capped backoff, a permanent failure raising
        ``ShardTaskError`` or, with ``allow_partial``, leaving the shard
        missing.  A job with a deadline never sweeps (see ``_run_job``).

        Returns the shards not yet run, which ``_run_job`` hands to the
        pooled loop.  The sweep hands off as soon as the median task
        wall time reaches ``gil_floor_s`` (checked after the first task
        and again at doubling counts, so a job of long tasks leaves
        after one), or one task runs past the pool's straggler
        threshold.  The pooled loop inherits ``durations``, so its
        speculation can fire at once.  What the sweep gives up is a
        speculative duplicate of a stuck task it is running: in one
        process a sub-millisecond task that stalls is a stall of the
        interpreter, which a second thread cannot beat."""
        check_at = 1
        median = 0.0
        for n, sid in enumerate(ids):
            done, res, took = self._attempt(corpus, sid, fn, job, attempts)
            if not done:
                continue
            results[sid] = res
            durations.append(took)
            if len(durations) >= check_at:
                check_at *= 2
                median = float(np.median(durations))
                if median >= self.gil_floor_s:
                    return ids[n + 1:]
            if took > self.straggler_factor * max(median,
                                                  self.min_straggler_s):
                return ids[n + 1:]
        return []

    def _attempt(self, corpus, sid: int, fn, job: int,
                 attempts: Dict[int, int]) -> "tuple[bool, Any, float]":
        """Shard ``sid``'s task on the calling thread, attempted until
        it succeeds: both hooks on every attempt, up to ``max_retries``
        retries with the capped backoff, and a permanent failure raising
        ``ShardTaskError`` or, with ``allow_partial``, giving up.
        ``fn=None`` runs the hooks alone.  Returns (succeeded, result,
        seconds of the attempt that succeeded)."""
        while True:
            attempts[sid] += 1
            attempt = attempts[sid]
            t_run = time.perf_counter()
            try:
                if self.fault_hook is not None:
                    self.fault_hook(sid, attempt)
                if self.task_hook is not None:
                    self.task_hook(sid, attempt, job)
                res = fn(corpus.shards[sid]) if fn is not None else None
            except Exception as exc:
                _log.warning("shard %d task failed (attempt %d): %s: %s",
                             sid, attempt, type(exc).__name__, exc)
                if attempt <= self.max_retries:
                    self.stats["retries"] += 1
                    time.sleep(min(
                        self.retry_backoff_cap_s,
                        self.retry_backoff_s * 2.0 ** (attempt - 1)))
                    continue
                if not self.allow_partial:
                    raise ShardTaskError(
                        f"shard {sid} failed after {attempt} "
                        f"attempts") from exc
                return False, None, 0.0
            return True, res, time.perf_counter() - t_run

    def _run_pooled(
        self,
        pool: ThreadPoolExecutor,
        corpus,
        ids: list,
        todo: list,
        fn: Callable[[Any], Any],
        job: int,
        deadline: Optional[float],
        results: Dict[int, Any],
        attempts: Dict[int, int],
        durations: list,
    ) -> bool:
        """The pooled loop: submit ``todo`` to the warm pool and collect
        into ``results`` / ``durations``, with retries, speculation and
        the deadline.  Returns whether the deadline expired."""
        epoch = self._job_epoch
        lock = threading.Lock()

        # live[sid][attempt] = when that attempt actually began executing
        # on a worker (NOT when it was submitted): with queue depth >>
        # workers, submission age measures queue wait, and the straggler
        # check would speculatively duplicate nearly every queued task
        # once the median of the first few completions is small.  Keyed
        # per attempt so a speculative duplicate cannot overwrite the
        # original's start (which would corrupt duration samples), and
        # failed attempts are removed so a queued retry is never
        # mistaken for a running straggler.
        live: Dict[int, Dict[int, float]] = {i: {} for i in ids}

        def run_one(sid: int, attempt: int) -> Any:
            with lock:
                live[sid][attempt] = time.perf_counter()
            if self.fault_hook is not None:
                self.fault_hook(sid, attempt)
            if self.task_hook is not None:
                self.task_hook(sid, attempt, job)
            return fn(corpus.shards[sid])

        completions = self._completions
        in_flight = 0
        speculated: set = set()
        # retries waiting out their backoff: heap of (due_time, sid)
        delayed: list = []

        def submit(sid: int) -> None:
            nonlocal in_flight
            with lock:
                attempts[sid] += 1
                attempt = attempts[sid]
            fut = pool.submit(run_one, sid, attempt)
            fut.add_done_callback(
                lambda f, sid=sid, a=attempt: completions.put(
                    (epoch, sid, a, f)))
            in_flight += 1

        def schedule_retry(sid: int) -> None:
            """The r-th retry of a shard waits backoff * 2^(r-1)
            (capped) before resubmission; zero backoff resubmits
            immediately, the legacy behavior."""
            self.stats["retries"] += 1
            if self.retry_backoff_s <= 0.0:
                submit(sid)
                return
            delay = min(self.retry_backoff_cap_s,
                        self.retry_backoff_s * 2.0 ** (attempts[sid] - 1))
            heapq.heappush(delayed, (time.perf_counter() + delay, sid))

        last_check = time.perf_counter()

        def check_stragglers(now: float) -> None:
            nonlocal last_check
            if len(durations) < self.min_completed:
                return
            if now - last_check < 0.05:  # O(ids) scan, throttled
                return
            last_check = now
            median = float(np.median(durations))
            threshold = self.straggler_factor * max(
                median, self.min_straggler_s)
            for sid in ids:
                if sid in results or sid in speculated:
                    continue
                with lock:
                    t_run = min(live[sid].values(), default=None)
                if t_run is not None and now - t_run > threshold:
                    speculated.add(sid)
                    self.stats["speculative"] += 1
                    submit(sid)

        # On permanent failure the error is *recorded*, submissions stop,
        # and the loop still drains every in-flight future before the
        # exception escapes — the old per-job pool got this quiescence
        # from its `with` shutdown; the shared warm pool must not be
        # left running zombie tasks that would queue-jam the next job.
        # A *deadline* expiry is the one exception: draining would let a
        # stalled task hold the job hostage past its own time bound, so
        # the job abandons its in-flight futures on the warm pool and
        # the epoch guard disposes of their late completions.
        fatal: Optional[ShardTaskError] = None
        timed_out = False
        for sid in todo:
            submit(sid)
        while in_flight or delayed:
            now = time.perf_counter()
            if fatal is None and deadline is not None and now >= deadline:
                timed_out = True
                break
            if fatal is None:
                while delayed and delayed[0][0] <= now:
                    _, sid = heapq.heappop(delayed)
                    submit(sid)
                if not in_flight and not delayed:
                    break
            timeout = 0.05
            if delayed and fatal is None:
                timeout = min(timeout, max(1e-4, delayed[0][0] - now))
            if deadline is not None and fatal is None:
                timeout = min(timeout, max(1e-4, deadline - now))
            if not in_flight:
                if fatal is not None:
                    break          # only delayed retries left: drop them
                time.sleep(timeout)
                continue
            try:
                rec_epoch, sid, attempt, fut = completions.get(
                    timeout=timeout)
            except queue.Empty:
                if fatal is None:
                    check_stragglers(time.perf_counter())
                continue
            if rec_epoch != epoch:
                # zombie from an abandoned (deadline-expired) earlier
                # job finishing late — drop, never decrement in_flight
                self.stats["stale_completions"] += 1
                continue
            in_flight -= 1
            now = time.perf_counter()
            try:
                res = fut.result()
                with lock:
                    t_start = live[sid].pop(attempt, now)
                if sid not in results:
                    results[sid] = res
                    durations.append(now - t_start)
            except Exception as exc:
                _log.warning("shard %d task failed (attempt %d): %s: %s",
                             sid, attempt, type(exc).__name__, exc)
                with lock:
                    live[sid].pop(attempt, None)
                if sid in results or fatal is not None:
                    pass  # a speculative duplicate failed after the
                          # original already delivered, or the job is
                          # already failing — nothing to redo
                elif attempts[sid] <= self.max_retries:
                    schedule_retry(sid)
                elif not self.allow_partial:
                    fatal = ShardTaskError(
                        f"shard {sid} failed after "
                        f"{attempts[sid]} attempts")
                # with allow_partial the shard stays missing: the job
                # degrades instead of failing
            if fatal is None:
                check_stragglers(now)
        if fatal is not None:
            raise fatal
        return timed_out

    def _run_group_scan(self, corpus, shard_ids: Sequence[int], fn,
                        spec) -> Dict[int, Any]:
        """The group route: ``spec.run_group`` answers the composite's
        grouped (query, shard) pairs for the whole shard group in one
        call instead of one task per shard.  Two fault contracts:

        * a host-side group scan (the composite has ``per_shard``, see
          ``run_shared_scan``) keeps today's per-shard one exactly.
          The job first runs ``per_shard`` — the tasks of the queries
          left outside the group, often none — as an ordinary per-shard
          job (``_run_job``: the sweep, the hooks on every shard and
          attempt, a failing shard retried alone with its backoff, and
          under ``allow_partial`` lost alone), then one group pass over
          the shards that completed, on the calling thread;
          ``last_job`` is that job's, its wall time taken to the end of
          the pass.
        * the one-launch megakernel (``MegascanSpec``): the hook pair
          fires for every shard in the group before the launch, so chaos
          scripts targeting individual shards still bite, but
          failure/retry is at-least-once at *group* granularity: any
          hook raise or launch failure re-runs the whole group (with the
          same bounded-exponential backoff), which is exactly the
          composite-task semantics ``map_shard_batch`` already
          documents, at width = whole group."""
        per_shard = getattr(fn, "per_shard", None)
        if per_shard is not None:
            t_job = time.perf_counter()
            results = self._run_job(corpus, shard_ids, per_shard,
                                    fn.hooks_only)
            finish_group_scan(fn, results)
            self.last_job["wall_s"] = time.perf_counter() - t_job
            return results
        ids = [int(s) for s in shard_ids]
        t_job = time.perf_counter()
        deadline = (t_job + self.job_deadline_s
                    if self.job_deadline_s is not None else None)
        self._job_epoch += 1
        job = self.stats["jobs"]
        if self.job_hook is not None:
            self.job_hook(job)
        queries_of = getattr(fn, "queries_of", None)
        if queries_of is None:
            queries_of = {sid: [] for sid in ids}
        attempt = 0
        lost: list = []
        results: Dict[int, Any] = {}
        while True:
            attempt += 1
            try:
                for sid in ids:
                    if self.fault_hook is not None:
                        self.fault_hook(sid, attempt)
                    if self.task_hook is not None:
                        self.task_hook(sid, attempt, job)
                results = spec.run_group(ids, queries_of)
                break
            except Exception as exc:
                _log.warning("megascan group of %d shards failed "
                             "(attempt %d): %s: %s", len(ids), attempt,
                             type(exc).__name__, exc)
                if attempt > self.max_retries:
                    raise ShardTaskError(
                        f"megascan group {ids} failed after "
                        f"{attempt} attempts") from exc
                self.stats["retries"] += 1
                delay = 0.0
                if self.retry_backoff_s > 0.0:
                    delay = min(self.retry_backoff_cap_s,
                                self.retry_backoff_s * 2.0 ** (attempt - 1))
                if deadline is not None and (
                        time.perf_counter() + delay >= deadline):
                    if self.allow_partial:
                        lost = list(ids)
                        break
                    raise ShardTaskError(
                        f"job deadline ({self.job_deadline_s}s) expired; "
                        f"megascan group incomplete: {ids}") from exc
                if delay > 0.0:
                    time.sleep(delay)
        self.stats["lost_shards"] += len(lost)
        self.stats["jobs"] += 1
        self.stats["megascan_jobs"] += 1
        wall = time.perf_counter() - t_job
        # median_task_s is what the window controller amortizes per
        # shard; with one launch for the group the honest attribution
        # is the launch wall spread over its shards
        self.last_job = {
            "wall_s": wall,
            "tasks": float(len(ids)),
            "median_task_s": wall / max(1, len(ids)),
            "lost_shards": float(len(lost)),
        }
        if spec.last_record is not None and not lost:
            self.last_job["megascan"] = dict(spec.last_record)
        return results

    def map_shard_batch(
        self,
        corpus,
        plan: Sequence[Sequence[int]],
        fns: Sequence[Callable[[Any], Any]],
        *,
        megakernel: Optional[bool] = None,
    ) -> "list[Dict[int, Any]]":
        """Shared scan over a batch of queries.

        ``plan[i]`` is the shard ids query ``i`` sampled and ``fns[i]``
        its per-shard task.  Returns one ``{shard_id: result}`` dict per
        query — exactly what ``map_shards(corpus, plan[i], fns[i])``
        would have produced, but each shard in the union of the plans is
        visited once, with all interested queries evaluated in that
        single visit.  Retry and straggler speculation are inherited
        from ``map_shards`` at composite-task granularity.

        ``megakernel`` (None = auto): when the fns come from one
        ``MegascanSpec``, route the whole union as ONE Pallas launch
        (see ``run_shared_scan``); ``False`` pins the per-shard fused
        path — the bit-for-bit parity reference.
        """
        return run_shared_scan(self.map_shards, corpus, plan, fns,
                               megakernel=megakernel)
