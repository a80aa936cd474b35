"""Deterministic chaos harness: FaultPlan injection (seeded flaky /
slow / stall / crash), the executor's bounded-backoff retries,
per-job deadlines with graceful partial results, the job-epoch guard
against zombie completions, and property-style scenario sweeps
(scripted faults x join/drain timing) pinning gather parity and
zero lost queries against the single-executor reference."""
import time

import numpy as np
import pytest

from repro.core.queries import BatchQuery, QueryBatch, parse_boolean
from repro.runtime import (
    FaultPlan,
    FleetManager,
    HostGroupExecutor,
    PlacementMap,
    ShardTaskExecutor,
)
from repro.runtime.chaos import ChaosCrash, ChaosFault
from repro.runtime.executor import ShardTaskError


class _FakeShard:
    def __init__(self, i):
        self.shard_id = i


class _FakeCorpus:
    def __init__(self, n):
        self.shards = [_FakeShard(i) for i in range(n)]


# ----------------------------------------------------------------------
# FaultPlan determinism
# ----------------------------------------------------------------------
def test_flaky_faults_are_deterministic_and_cleared_by_retries():
    corpus = _FakeCorpus(24)

    def one_run():
        plan = FaultPlan(seed=3).flaky(0, error_rate=0.25)
        with ShardTaskExecutor(workers=4, max_retries=6) as ex:
            plan.install(ex)
            out = ex.map_shards(corpus, range(24),
                                lambda s: s.shard_id * 2)
            return out, plan.fired["flaky"], ex.stats["retries"]

    out1, fired1, retries1 = one_run()
    out2, fired2, retries2 = one_run()
    assert out1 == out2 == {i: i * 2 for i in range(24)}
    # decisions are a pure function of (seed, host, shard, job,
    # attempt) — identical across runs regardless of thread timing
    assert fired1 == fired2 > 0
    assert retries1 == retries2 == fired1   # every fault retried clear


def test_flaky_decision_is_coordinate_keyed_not_stream_keyed():
    plan_a = FaultPlan(seed=5).flaky(0, error_rate=0.5)
    plan_b = FaultPlan(seed=6).flaky(0, error_rate=0.5)
    hook_a, hook_b = plan_a._task_hook_for(0), plan_b._task_hook_for(0)

    def decisions(hook, plan):
        out = []
        plan._advance(0)
        for sid in range(40):
            try:
                hook(sid, 0, 0)
                out.append(False)
            except ChaosFault:
                out.append(True)
        return out

    da, db = decisions(hook_a, plan_a), decisions(hook_b, plan_b)
    assert da == decisions(hook_a, plan_a)   # replay-identical
    assert da != db                          # the seed is load-bearing


def test_crash_persists_and_stall_sleeps():
    plan = FaultPlan(seed=0).crash(1, at_job=2).stall(0, s=0.03, jobs=[1])
    plan._advance(1)
    t0 = time.perf_counter()
    plan._host_hook(0, [1, 2])               # stalls
    assert time.perf_counter() - t0 >= 0.025
    plan._host_hook(1, [3])                  # job 1 < at_job 2: alive
    plan._advance(2)
    with pytest.raises(ChaosCrash):
        plan._host_hook(1, [3])
    plan._advance(7)
    with pytest.raises(ChaosCrash):          # dead stays dead
        plan._host_hook(1, [3])
    assert plan.fired["crash"] == 2 and plan.fired["stall"] == 1
    rec = plan.record()
    assert rec["scripted"]["crashes"] == [[1, 2]]
    assert rec["fired"]["crash"] == 2


# ----------------------------------------------------------------------
# executor hardening: backoff, deadline, epoch guard
# ----------------------------------------------------------------------
def test_retry_backoff_delays_resubmission():
    corpus = _FakeCorpus(4)
    failed = set()

    def flake_once(sid, attempt, job):
        # attempts are 1-based: the first run of a shard is attempt 1
        if attempt == 1 and sid == 2 and 2 not in failed:
            failed.add(2)
            raise ChaosFault("one transient fault")

    with ShardTaskExecutor(workers=2, task_hook=flake_once,
                           retry_backoff_s=0.08) as ex:
        t0 = time.perf_counter()
        out = ex.map_shards(corpus, range(4), lambda s: s.shard_id)
        dt = time.perf_counter() - t0
    assert out == {i: i for i in range(4)}
    assert ex.stats["retries"] == 1
    assert dt >= 0.06            # the retry waited out the backoff


def test_backoff_is_bounded_by_cap():
    corpus = _FakeCorpus(1)

    def always_fail(sid, attempt, job):
        raise ChaosFault(f"attempt {attempt}")

    with ShardTaskExecutor(workers=1, max_retries=3,
                           task_hook=always_fail,
                           retry_backoff_s=0.01,
                           retry_backoff_cap_s=0.02) as ex:
        t0 = time.perf_counter()
        with pytest.raises(ShardTaskError):
            ex.map_shards(corpus, [0], lambda s: s.shard_id)
        dt = time.perf_counter() - t0
    # 3 retries at 0.01 / 0.02 / 0.02 (capped, not 0.04): well under
    # the uncapped geometric sum's wall
    assert ex.stats["retries"] == 3
    assert 0.04 <= dt < 0.5


def test_job_deadline_returns_partial_when_allowed():
    corpus = _FakeCorpus(6)

    def slow_tail(sid, attempt, job):
        if sid >= 4:
            time.sleep(0.5)

    with ShardTaskExecutor(workers=2, task_hook=slow_tail,
                           job_deadline_s=0.15,
                           allow_partial=True) as ex:
        out = ex.map_shards(corpus, range(6), lambda s: s.shard_id)
        # the fast shards landed; the stalled tail was abandoned at
        # the deadline instead of holding the job open
        assert set(out) == {0, 1, 2, 3}
        assert ex.stats["lost_shards"] == 2
        assert ex.last_job["lost_shards"] == 2.0


def test_job_deadline_raises_without_allow_partial():
    corpus = _FakeCorpus(2)

    def stall_all(sid, attempt, job):
        time.sleep(0.5)

    with ShardTaskExecutor(workers=2, task_hook=stall_all,
                           job_deadline_s=0.05) as ex:
        with pytest.raises(ShardTaskError, match="deadline"):
            ex.map_shards(corpus, range(2), lambda s: s.shard_id)


def test_zombie_completion_from_abandoned_job_is_dropped():
    corpus = _FakeCorpus(2)
    stall_first_job = {"on": True}

    def hook(sid, attempt, job):
        if stall_first_job["on"]:
            time.sleep(0.3)

    with ShardTaskExecutor(workers=1, task_hook=hook,
                           job_deadline_s=0.05,
                           allow_partial=True) as ex:
        out1 = ex.map_shards(corpus, [0], lambda s: s.shard_id)
        assert out1 == {}                    # abandoned at the deadline
        stall_first_job["on"] = False
        time.sleep(0.5)                      # zombie finishes, enqueues
        # the next job must not see the stale epoch's completion
        out2 = ex.map_shards(corpus, [1], lambda s: s.shard_id + 10)
        assert out2 == {1: 11}
        assert ex.stats["stale_completions"] >= 1


# ----------------------------------------------------------------------
# property-style scenario sweep: scripted faults x membership timing,
# pinned invariants — gather parity vs the single executor on every
# batch and zero lost queries (a replica survives every scenario)
# ----------------------------------------------------------------------
def _mixed_queries():
    return [
        BatchQuery.count([3]),
        BatchQuery.boolean(parse_boolean([3, "or", 5, "and", 9])),
        BatchQuery.ranked([7, 4, 5], k=10),
        BatchQuery.count([11]),
    ]


def _assert_results_identical(got, want):
    for g, w in zip(got, want):
        assert type(g) is type(w)
        if hasattr(g, "doc_ids"):
            np.testing.assert_array_equal(g.doc_ids, w.doc_ids)
            if hasattr(g, "scores"):
                np.testing.assert_array_equal(g.scores, w.scores)
        else:
            assert g.estimate.value == w.estimate.value
            assert g.estimate.error_bound == w.estimate.error_bound


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("scenario",
                         ["crash_then_join", "drain_mid_stream",
                          "flaky_everywhere", "stall_and_slow"])
def test_chaos_scenarios_preserve_parity_and_lose_nothing(
        small_corpus, built_index, scenario, seed):
    queries = _mixed_queries()
    pm = PlacementMap.blocked(small_corpus.n_shards, 2, n_replicas=1)
    with ShardTaskExecutor(workers=2) as single, \
            HostGroupExecutor(pm, workers_per_host=1, max_retries=6,
                              allow_partial=True) as hg:
        ref = QueryBatch(small_corpus, built_index, executor=single)
        engine = QueryBatch(small_corpus, built_index, executor=hg)
        plan = FaultPlan(seed=seed)
        fleet = FleetManager(hg)
        # membership ops keyed on batch index: fired between batches,
        # mimicking a failure detector / operator acting mid-stream
        ops = {}
        if scenario == "crash_then_join":
            plan.crash(1, at_job=1)          # batch 1 discovers it live
            ops[1] = lambda: fleet.crash(1)  # detector catches up after
            ops[2] = lambda: fleet.join(2)   # replacement host joins
        elif scenario == "drain_mid_stream":
            ops[1] = lambda: fleet.drain(0)
        elif scenario == "flaky_everywhere":
            plan.flaky(0, error_rate=0.2).flaky(1, error_rate=0.2)
        else:
            plan.stall(0, s=0.02, jobs=[1]).slow(1, ms_per_shard=1.0)
        plan.install(hg)
        for batch in range(4):
            rng_seed = 100 * seed + batch
            got = engine.execute(queries, 0.5,
                                 rng=np.random.default_rng(rng_seed))
            want = ref.execute(queries, 0.5,
                               rng=np.random.default_rng(rng_seed))
            _assert_results_identical(got, want)
            assert engine.last_report.degraded is None
            if batch in ops:
                ops[batch]()
        assert hg.stats["lost_shards"] == 0
        if scenario == "crash_then_join":
            assert plan.fired["crash"] >= 1   # the kill landed
