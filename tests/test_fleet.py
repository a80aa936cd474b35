"""Elastic fleet membership: FleetManager join/drain/crash over the
placement layer — RCU generation swaps, warm-before-serve joins,
orphan bookkeeping and revival, the cold-join load-model pricing, and
the all-replicas-dead degraded path through QueryBatch (the regression
pin for the former bare-HostFailure crash)."""
import json

import numpy as np
import pytest

from repro.core.queries import BatchQuery, QueryBatch, parse_boolean
from repro.runtime import (
    FleetManager,
    HostFailure,
    HostGroupExecutor,
    PlacementMap,
    ShardTaskExecutor,
)
from repro.runtime.balance import HostLoadModel


class _FakeShard:
    def __init__(self, i):
        self.shard_id = i


class _FakeCorpus:
    def __init__(self, n):
        self.shards = [_FakeShard(i) for i in range(n)]


def _ids(corpus, hg):
    return hg.map_shards(corpus, range(len(corpus.shards)),
                         lambda s: s.shard_id)


# ----------------------------------------------------------------------
# drain / crash: one transfer path, two orderings
# ----------------------------------------------------------------------
def test_drain_moves_residency_then_retires():
    corpus = _FakeCorpus(12)
    with HostGroupExecutor(PlacementMap.blocked(12, 3, n_replicas=1),
                           workers_per_host=1) as hg:
        fleet = FleetManager(hg)
        ev = fleet.drain(1)
        assert ev["op"] == "drain" and ev["planned"] is True
        assert ev["moved_shards"] == 4 and ev["orphaned_shards"] == 0
        assert 1 in hg.down
        assert not (hg.placement.primary == 1).any()
        assert hg.clock.current().placement == 1
        assert fleet.live_hosts() == [0, 2]
        # serving continues on the survivors, nothing lost
        out = _ids(corpus, hg)
        assert sorted(out) == list(range(12))
        assert hg.stats["lost_shards"] == 0


def test_crash_transfers_with_planned_false():
    corpus = _FakeCorpus(12)
    with HostGroupExecutor(PlacementMap.blocked(12, 3, n_replicas=1),
                           workers_per_host=1) as hg:
        fleet = FleetManager(hg)
        ev = fleet.crash(2)
        assert ev["op"] == "crash" and ev["planned"] is False
        assert ev["moved_shards"] == 4 and ev["orphaned_shards"] == 0
        assert sorted(_ids(corpus, hg)) == list(range(12))
        rec = fleet.record()
        assert rec["crashes"] == 1 and rec["joins"] == 0
        json.dumps(rec)                      # audit is JSON-ready


def test_join_grows_fleet_warm_before_residency():
    corpus = _FakeCorpus(12)
    with HostGroupExecutor(PlacementMap.blocked(12, 2, n_replicas=1),
                           workers_per_host=1) as hg:
        streamed = []

        def warm(sid, src, dst):
            # residency must not have swapped yet: the joiner owns
            # nothing while its shards are still streaming
            assert not (hg.placement.primary == dst).any()
            streamed.append((sid, src, dst))

        fleet = FleetManager(hg, warm_fn=warm)
        ev = fleet.join()
        assert ev["host"] == 2               # fleet grew by one id
        assert ev["warmed_shards"] == len(streamed) == 4
        counts = [int((hg.placement.primary == h).sum()) for h in range(3)]
        assert counts == [4, 4, 4]           # stolen down to even share
        assert sorted(_ids(corpus, hg)) == list(range(12))


def test_join_revives_down_slot_and_its_orphans():
    corpus = _FakeCorpus(8)
    # no replicas: a crash orphans the dead host's shards
    with HostGroupExecutor(PlacementMap.blocked(8, 2, n_replicas=0),
                           workers_per_host=1, allow_partial=True) as hg:
        fleet = FleetManager(hg)
        ev = fleet.crash(1)
        assert ev["orphaned_shards"] == 4 and ev["moved_shards"] == 0
        out = _ids(corpus, hg)
        assert sorted(out) == [0, 1, 2, 3]   # partial: orphans lost
        assert hg.stats["lost_shards"] == 4
        # default join revives the lowest down slot — and the orphaned
        # shards, which kept their dead primary, come back with it
        ev = fleet.join()
        assert ev["host"] == 1
        assert sorted(_ids(corpus, hg)) == list(range(8))


def test_fleet_lifecycle_epochs_and_audit():
    with HostGroupExecutor(PlacementMap.blocked(12, 2, n_replicas=1),
                           workers_per_host=1) as hg:
        fleet = FleetManager(hg)
        fleet.crash(1)
        fleet.join(2)
        fleet.drain(0)
        rec = fleet.record()
        assert [e["op"] for e in rec["events"]] == ["crash", "join",
                                                    "drain"]
        assert rec["placement_epoch"] == 3   # one generation per op
        assert rec["live_hosts"] == [2]


# ----------------------------------------------------------------------
# cold-join pricing in the load model
# ----------------------------------------------------------------------
def test_load_model_prices_cold_host_at_fleet_median():
    m = HostLoadModel(2)
    m.observe(0, wall_s=0.2, n_shards=2)     # 0.1 s/shard
    m.observe(1, wall_s=0.6, n_shards=2)     # 0.3 s/shard
    m.ensure_hosts(3)                        # joiner: no telemetry
    cold = m.shard_cost(2)
    assert cold == pytest.approx(np.median([0.1, 0.3]))
    m.forget_host(0)                         # departed: telemetry drops
    assert m.shard_cost(0) == pytest.approx(m.shard_cost(2))


# ----------------------------------------------------------------------
# all-replicas-dead: typed partial results, not a bare crash
# (regression pin for the former uncaught HostFailure)
# ----------------------------------------------------------------------
def _queries():
    return [
        BatchQuery.count([3]),
        BatchQuery.boolean(parse_boolean([3, "or", 5, "and", 9])),
        BatchQuery.ranked([7, 4, 5], k=10),
    ]


def test_all_replicas_dead_raises_typed_without_allow_partial(
        small_corpus, built_index):
    pm = PlacementMap.blocked(small_corpus.n_shards, 2, n_replicas=0)
    with HostGroupExecutor(pm, workers_per_host=1) as hg:
        FleetManager(hg).crash(1)
        engine = QueryBatch(small_corpus, built_index, executor=hg)
        with pytest.raises(HostFailure):
            engine.execute(_queries(), 0.9,
                           rng=np.random.default_rng(0))


def test_all_replicas_dead_degrades_to_partial_estimates(
        small_corpus, built_index):
    pm = PlacementMap.blocked(small_corpus.n_shards, 2, n_replicas=0)
    with ShardTaskExecutor(workers=2) as single, \
            HostGroupExecutor(pm, workers_per_host=1,
                              allow_partial=True) as hg:
        ref = QueryBatch(small_corpus, built_index, executor=single)
        want = ref.execute(_queries(), 0.9, rng=np.random.default_rng(1))
        engine = QueryBatch(small_corpus, built_index, executor=hg)
        FleetManager(hg).crash(1)
        got = engine.execute(_queries(), 0.9,
                             rng=np.random.default_rng(1))
        deg = engine.last_report.degraded
        assert deg is not None and deg["lost_shards"] > 0
        assert deg["degraded_queries"] >= 1
        # count: reduced over the surviving draws only — imprecise,
        # wider CI than the healthy reference, loss accounted
        count_got, count_want = got[0], want[0]
        assert count_got.lost_shards > 0
        assert count_got.estimate.error_bound > 0.0
        assert count_got.shards_read < count_want.shards_read
        # the estimator reduces over the surviving draws only (the CI
        # widens in expectation, not pointwise — variance is
        # data-dependent — so pin the sample shrink, not the bound)
        assert count_got.estimate.n < count_want.estimate.n
        # retrieval: served from surviving shards, loss surfaced
        assert got[1].lost_shards > 0 or got[2].lost_shards > 0
        for g in got[1:]:
            assert len(g.doc_ids) <= small_corpus.n_docs


def test_healthy_fleet_reports_no_degradation(small_corpus, built_index):
    pm = PlacementMap.blocked(small_corpus.n_shards, 2, n_replicas=1)
    with HostGroupExecutor(pm, workers_per_host=1,
                           allow_partial=True) as hg:
        engine = QueryBatch(small_corpus, built_index, executor=hg)
        got = engine.execute(_queries(), 0.9, rng=np.random.default_rng(2))
        assert engine.last_report.degraded is None
        assert all(g.lost_shards == 0 for g in got)
