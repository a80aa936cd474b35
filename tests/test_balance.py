"""Replica-aware load balancing: HostLoadModel telemetry, the
cost-aware ``PlacementMap.split`` replica paths (hot-primary shed,
hysteresis, dead-primary unification with the requeue path), and
end-to-end bit-for-bit parity of balanced execution with the
single-executor reduce under an injected slow host."""
import time

import numpy as np
import pytest

from repro.core.queries import BatchQuery, QueryBatch, parse_boolean
from repro.runtime import (
    BalanceConfig,
    HostFailure,
    HostGroupExecutor,
    HostLoadModel,
    PlacementMap,
    ShardTaskExecutor,
    plan_split,
)


class _FakeShard:
    def __init__(self, i):
        self.shard_id = i


class _FakeCorpus:
    def __init__(self, n):
        self.shards = [_FakeShard(i) for i in range(n)]


def _hot_model(hot_cost=0.2, cold_cost=0.01, n_hosts=2):
    m = HostLoadModel(n_hosts)
    m.observe(0, hot_cost * 4, 4)
    for h in range(1, n_hosts):
        m.observe(h, cold_cost * 4, 4)
    return m


# ----------------------------------------------------------------------
# HostLoadModel
# ----------------------------------------------------------------------
def test_load_model_seeds_uniform_before_telemetry():
    m = HostLoadModel(3)
    costs = [m.shard_cost(h) for h in range(3)]
    assert costs[0] == costs[1] == costs[2] > 0
    # uniform prior => estimated host load is just the shard count, so
    # the cold balanced split degenerates to count balancing
    pm = PlacementMap.blocked(12, 3, n_replicas=1)
    audit = plan_split(pm, range(12), m)
    assert audit.groups == pm.split(range(12))


def test_load_model_ewma_and_median_seeding():
    m = HostLoadModel(3, BalanceConfig(ewma_alpha=0.5))
    m.observe(0, 1.0, 10)                    # 100 ms/shard
    assert m.shard_cost(0) == pytest.approx(0.1)
    m.observe(0, 2.0, 10)                    # EWMA toward 200 ms/shard
    assert m.shard_cost(0) == pytest.approx(0.15)
    # a host without telemetry prices at the fleet median, not the seed
    assert m.shard_cost(1) == pytest.approx(m.shard_cost(0))
    m.observe(1, 0.1, 10)
    assert m.snapshot()[2] is None
    assert m.shard_cost(2) == pytest.approx(
        float(np.median([0.15, 0.01])))


def test_load_model_validation():
    with pytest.raises(ValueError):
        HostLoadModel(0)
    with pytest.raises(ValueError):
        BalanceConfig(ewma_alpha=0.0)
    with pytest.raises(ValueError):
        BalanceConfig(hysteresis=-0.1)
    m = HostLoadModel(2)
    m.observe(0, 1.0, 0)                     # no-op, not a crash
    assert m.snapshot() == [None, None]


# ----------------------------------------------------------------------
# cost-aware split: shed, hysteresis, dead-host unification
# ----------------------------------------------------------------------
def test_hot_primary_sheds_to_live_replica_and_preserves_residency():
    pm = PlacementMap.blocked(16, 2, n_replicas=1)
    m = _hot_model()
    audit = plan_split(pm, range(16), m)
    assert audit.balanced and audit.shed > 0
    # the hot host kept less than its residency half
    sizes = {h: len(g) for h, g in audit.groups.items()}
    assert sizes.get(0, 0) < 8
    # residency preserved: every shard landed on a host that holds it
    for h, g in audit.groups.items():
        for sid in g:
            assert h in pm.hosts_of(sid)
    # all shards assigned exactly once
    assert sorted(s for g in audit.groups.values() for s in g) == \
        list(range(16))
    # the balanced estimate beats the residency estimate
    assert audit.est_makespan_s < audit.est_base_makespan_s
    # split(load=...) is the same assignment
    assert pm.split(range(16), load=m) == audit.groups


def test_shed_lands_on_ring_replica_with_more_hosts():
    # 4 hosts, R=1: shards of host 0 may only go to 0 or its ring
    # replica 1 — never to 2 or 3, however cold those are
    pm = PlacementMap.blocked(16, 4, n_replicas=1)
    m = HostLoadModel(4)
    m.observe(0, 4.0, 4)                     # scorching
    for h in (1, 2, 3):
        m.observe(h, 0.04, 4)
    groups = pm.split(range(16), load=m)
    for h, g in groups.items():
        for sid in g:
            assert h in pm.hosts_of(sid)
    # the scorching host keeps nothing: its shards all shed to their
    # ring replica (host 1), which may cascade its own load onward —
    # but never onto a host that lacks the data
    assert len(groups.get(0, [])) == 0
    for sid in pm.shards_on(0):
        assert sid in groups[1]


def test_hysteresis_suppresses_flapping_under_near_equal_load():
    pm = PlacementMap.blocked(16, 2, n_replicas=1)
    m = HostLoadModel(2, BalanceConfig(hysteresis=0.25))
    m.observe(0, 0.44, 4)                    # 110 ms/shard
    m.observe(1, 0.40, 4)                    # 100 ms/shard: ~10% apart
    audits = [plan_split(pm, range(16), m) for _ in range(3)]
    for a in audits:
        assert not a.balanced and a.shed == 0
        assert a.groups == pm.split(range(16))
        assert a.est_makespan_s == a.est_base_makespan_s
    # widening the gap past the band flips it — the band, not the
    # model, was holding the split steady
    m2 = _hot_model(hot_cost=0.2, cold_cost=0.01)
    assert plan_split(pm, range(16), m2).balanced


def test_hysteresis_is_stateful_asymmetric_band():
    """Real hysteresis: the keep/shed decision depends on the previous
    decision.  A gap between the stay and enter thresholds keeps
    whatever split is already running — a fresh model at the same gap
    stays primary, a model already balanced stays balanced."""
    pm = PlacementMap.blocked(16, 2, n_replicas=1)
    cfg = BalanceConfig(hysteresis=0.25, stay_fraction=0.5, ewma_alpha=1.0)

    def observe_gap(m, ratio):
        m.observe(0, 0.1 * ratio * 4, 4)     # host 0 at ratio x host 1
        m.observe(1, 0.1 * 4, 4)

    # base makespan 8*c0, balanced ~ interleaves; pick a gross gap to
    # enter balanced mode first
    m = HostLoadModel(2, cfg)
    observe_gap(m, 20.0)
    assert plan_split(pm, range(16), m).balanced and m.balanced_mode
    # now hover between the stay (12.5%) and enter (25%) thresholds:
    # the balanced model keeps shedding ...
    observe_gap(m, 1.37)                     # est_base/est_bal ~ 1.2
    a_stay = plan_split(pm, range(16), m)
    assert a_stay.balanced and m.balanced_mode
    # ... while a fresh model at the identical load keeps the
    # residency split — same inputs, different (previous) state
    m2 = HostLoadModel(2, cfg)
    observe_gap(m2, 1.37)
    a_enter = plan_split(pm, range(16), m2)
    assert not a_enter.balanced and not m2.balanced_mode
    # dropping under the stay threshold exits balanced mode
    observe_gap(m, 1.0)
    assert not plan_split(pm, range(16), m).balanced
    assert not m.balanced_mode


def test_balanced_split_minimizes_churn():
    """Per-shard cost is host-uniform, so the balanced split should
    never *cross-move* shards: when the hot host keeps capacity worth
    using, it uses its own resident shards, not the cold host's."""
    pm = PlacementMap.blocked(16, 2, n_replicas=1)
    m = HostLoadModel(2, BalanceConfig(ewma_alpha=1.0))
    m.observe(0, 0.3 * 4, 4)                 # host 0 is 3x host 1
    m.observe(1, 0.1 * 4, 4)
    audit = plan_split(pm, range(16), m)
    assert audit.balanced
    sizes = {h: len(g) for h, g in audit.groups.items()}
    assert 0 < sizes[0] < 8                  # hot host still used
    base_host = {sid: h for h, g in audit.base_groups.items()
                 for sid in g}
    # no bidirectional churn: at most one direction of movement exists
    to_hot = [s for s in audit.groups[0] if base_host[s] == 1]
    off_hot = [s for s in audit.groups[1] if base_host[s] == 0]
    assert not (to_hot and off_hot)
    # shed equals the minimum possible for these group sizes
    assert audit.shed == abs(sizes[0] - len(audit.base_groups[0]))


def test_requeued_host_wall_accumulates_across_rounds():
    """A host that ran its own group and then absorbed a dead host's
    requeued group spent both walls — per_host_wall_s must report the
    sum, not just the last round (the audit the bench compares
    est-vs-realized against)."""
    pm = PlacementMap.blocked(10, 2, n_replicas=1)

    def hook(host, shard_ids):
        if host == 0:
            raise RuntimeError("host 0 down")
        time.sleep(0.01 * len(shard_ids))    # 10 ms per shard on host 1

    with HostGroupExecutor(pm, workers_per_host=1,
                           host_fault_hook=hook) as hg:
        out = hg.map_shards(_FakeCorpus(10), range(10), lambda s: 1)
    assert len(out) == 10
    # host 1 ran its own 5 shards, then host 0's requeued 5: >= 100 ms
    assert hg.last_job["per_host_wall_s"][1] >= 0.09


def test_dead_primary_requeue_and_balancer_shed_are_identical():
    """Failover is balancing with an infinite cost: for R=1 the
    balancer's dead-host split must equal the primary-only requeue
    split, whatever the load model says."""
    pm = PlacementMap.blocked(16, 2, n_replicas=1)
    ids = [3, 0, 9, 12, 5]
    dead = frozenset({0})
    want = pm.split(ids, dead)
    for model in (HostLoadModel(2), _hot_model(),
                  _hot_model(hot_cost=0.01, cold_cost=0.2)):
        assert pm.split(ids, dead, load=model) == want
    # both hosts dead: same HostFailure either way
    with pytest.raises(HostFailure):
        pm.split(ids, frozenset({0, 1}), load=_hot_model())


def test_balanced_executor_requeues_dead_host_like_primary_split():
    """End-to-end: an executor-killed host routes through the same
    balancer split — every shard re-runs on the surviving replica,
    exactly as the primary-only requeue does."""
    pm = PlacementMap.blocked(10, 2, n_replicas=1)

    def host_fault(host, shard_ids):
        if host == 0:
            raise RuntimeError("host 0 down")

    with HostGroupExecutor(pm, workers_per_host=1, balanced=True,
                           host_fault_hook=host_fault) as hg:
        out = hg.map_shards(_FakeCorpus(10), range(10),
                            lambda s: s.shard_id + 1)
    assert out == {i: i + 1 for i in range(10)}
    assert hg.stats["host_failures"] == 1
    assert hg.stats["scans_per_host"] == [0, 10]


def test_requeue_round_is_read_only_on_hysteresis_state():
    """A transient host death mid-job splits only the dead host's
    group; that degenerate subset must not flip ``balanced_mode`` (or
    inflate the planned-shed stat) — otherwise one blip resets the
    asymmetric band and the next planned split flaps."""
    pm = PlacementMap.blocked(16, 2, n_replicas=1)
    model = _hot_model()                     # host 0 hot: planned split
    assert plan_split(pm, range(16), model).balanced   # sheds to host 1
    assert model.balanced_mode

    died = []

    def fault(host, shard_ids):
        if host == 1 and not died:           # kill the cold host once
            died.append(host)
            raise RuntimeError("host 1 down")

    with HostGroupExecutor(pm, workers_per_host=1, balancer=model,
                           host_fault_hook=fault) as hg:
        out = hg.map_shards(_FakeCorpus(16), range(16), lambda s: 1)
        planned_shed = hg.last_job["balance"]["shed"]
    assert len(out) == 16 and died == [1]
    # the requeue (everything forced onto host 0, a no-choice split
    # whose base == balanced) left the hysteresis state alone ...
    assert model.balanced_mode
    # ... and the shed stat counts only the planned split's moves
    assert hg.stats["shed_shards"] == planned_shed


# ----------------------------------------------------------------------
# HostGroupExecutor with a balancer: telemetry, audit, convergence
# ----------------------------------------------------------------------
def test_balanced_executor_learns_and_sheds_hot_host():
    pm = PlacementMap.blocked(16, 2, n_replicas=1)

    def hot(host, shard_ids):                # host 0 is 5 ms/shard slower
        if host == 0:
            time.sleep(0.005 * len(shard_ids))

    with HostGroupExecutor(pm, workers_per_host=1, balanced=True,
                           host_fault_hook=hot) as hg:
        walls = []
        for _ in range(3):
            out = hg.map_shards(_FakeCorpus(16), range(16),
                                lambda s: s.shard_id)
            assert out == {i: i for i in range(16)}
            walls.append(hg.last_job["balance"]["realized_makespan_s"])
        rec = hg.last_job["balance"]
    # first job runs the seeded (count-balanced) split, later jobs shed
    assert hg.stats["shed_shards"] > 0
    assert rec["balanced"] and rec["shed"] > 0
    assert rec["group_sizes"][0] < rec["base_group_sizes"][0]
    assert sum(rec["realized_group_sizes"]) == 16
    # the balanced split beats the hot residency split's makespan
    assert walls[-1] < walls[0]
    assert rec["est_base_makespan_s"] > rec["est_makespan_s"]


def test_balance_record_absent_without_balancer():
    pm = PlacementMap.blocked(8, 2, n_replicas=1)
    with HostGroupExecutor(pm, workers_per_host=1) as hg:
        hg.map_shards(_FakeCorpus(8), range(8), lambda s: s.shard_id)
        assert "balance" not in hg.last_job
        assert hg.stats["shed_shards"] == 0


# ----------------------------------------------------------------------
# end-to-end: balanced QueryBatch bit-for-bit vs single executor
# ----------------------------------------------------------------------
def _mixed_queries():
    return [
        BatchQuery.count([3]),
        BatchQuery.boolean(parse_boolean([3, "or", 5, "and", 9])),
        BatchQuery.ranked([7, 4, 5], k=10),
        BatchQuery.count([11]),
        BatchQuery.ranked([2, 10], k=5),
        BatchQuery.boolean(parse_boolean([2, "and", 7])),
    ]


def _assert_results_identical(got, want):
    for g, w in zip(got, want):
        assert type(g) is type(w)
        if hasattr(g, "doc_ids"):                   # retrieval / ranked
            np.testing.assert_array_equal(g.doc_ids, w.doc_ids)
            if hasattr(g, "scores"):                # RankedResult
                np.testing.assert_array_equal(g.scores, w.scores)
        else:                                       # PhraseCountResult
            assert g.estimate.value == w.estimate.value
            assert g.estimate.error_bound == w.estimate.error_bound
        assert g.shards_read == w.shards_read


def test_balanced_query_batch_matches_single_executor_under_slow_host(
        small_corpus, built_index):
    """The satellite requirement: with an injected slow-host fault the
    balancer sheds work onto the replica, and the gathered reduces for
    all three query kinds stay bit-for-bit the single-executor
    results."""
    queries = _mixed_queries()
    with ShardTaskExecutor(workers=2) as single:
        ref_engine = QueryBatch(small_corpus, built_index, executor=single)
        wants = [ref_engine.execute(queries, 0.5,
                                    rng=np.random.default_rng(21 + j))
                 for j in range(3)]

    def slow_host(host, shard_ids):          # host 0 drags 5 ms/shard
        if host == 0:
            time.sleep(0.005 * len(shard_ids))

    pm = PlacementMap.blocked(small_corpus.n_shards, 2, n_replicas=1)
    with HostGroupExecutor(pm, workers_per_host=1, balanced=True,
                           host_fault_hook=slow_host) as hg:
        engine = QueryBatch(small_corpus, built_index, executor=hg)
        for j, want in enumerate(wants):
            got = engine.execute(queries, 0.5,
                                 rng=np.random.default_rng(21 + j))
            _assert_results_identical(got, want)
        audit = engine.last_report.balance
    # the slow host was actually detected and shed around
    assert hg.stats["shed_shards"] > 0
    # the executed split is audited on the engine
    assert audit is not None and audit["balanced"]
    assert audit["group_sizes"][0] < audit["base_group_sizes"][0]
