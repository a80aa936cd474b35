"""Locality-aware multi-host execution: PlacementMap residency and
failover, HostGroupExecutor per-host shared scans + cross-host gather
parity with the single-executor path (bit-for-bit, including under an
injected host fault with replica requeue), and per-host scan-count
accounting against the union plan's residency split."""
import numpy as np
import pytest

from repro.core.queries import BatchQuery, QueryBatch, parse_boolean
from repro.launch.mesh import make_placement_mesh
from repro.runtime import (
    HostFailure,
    HostGroupExecutor,
    PlacementMap,
    ShardTaskExecutor,
)
from repro.runtime.executor import invert_plan


class _FakeShard:
    def __init__(self, i):
        self.shard_id = i


class _FakeCorpus:
    def __init__(self, n):
        self.shards = [_FakeShard(i) for i in range(n)]


# ----------------------------------------------------------------------
# PlacementMap
# ----------------------------------------------------------------------
def test_blocked_placement_is_contiguous_and_covering():
    pm = PlacementMap.blocked(16, 4, n_replicas=1)
    assert pm.n_shards == 16 and pm.n_hosts == 4 and pm.n_replicas == 1
    # contiguous blocks, every host owns a quarter
    np.testing.assert_array_equal(pm.primary, np.repeat(np.arange(4), 4))
    for h in range(4):
        np.testing.assert_array_equal(pm.shards_on(h),
                                      np.arange(4 * h, 4 * h + 4))


def test_round_robin_placement_stripes():
    pm = PlacementMap.round_robin(10, 3, n_replicas=2)
    np.testing.assert_array_equal(pm.primary, np.arange(10) % 3)
    for sid in range(10):
        hosts = pm.hosts_of(sid)
        assert len(hosts) == 3                  # primary + 2 replicas
        assert len(set(hosts)) == 3             # all distinct


def test_replicas_capped_and_distinct_from_primary():
    pm = PlacementMap.blocked(8, 2, n_replicas=5)   # only 1 other host
    assert pm.n_replicas == 1
    assert (pm.replicas[:, 0] != pm.primary).all()
    none = PlacementMap.blocked(8, 2, n_replicas=0)
    assert none.n_replicas == 0


def test_split_by_residency_and_failover_order():
    pm = PlacementMap.blocked(8, 2, n_replicas=1)   # 0-3 on h0, 4-7 on h1
    groups = pm.split([0, 5, 2, 7])
    assert groups == {0: [0, 2], 1: [5, 7]}
    # dead primary: shards fail over to the replica host
    assert pm.split([0, 5], dead=frozenset({0})) == {1: [0, 5]}
    with pytest.raises(HostFailure):
        pm.split([0], dead=frozenset({0, 1}))
    with pytest.raises(HostFailure):
        PlacementMap.blocked(8, 2, n_replicas=0).split(
            [1], dead=frozenset({0}))


def test_from_mesh_reads_residency_axes():
    pm = PlacementMap.from_mesh(make_placement_mesh(4), 10)
    assert pm.n_hosts == 4
    assert len(np.unique(pm.primary)) == 4
    # pod x data both count as residency axes
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((2, 3, 4), ("pod", "data", "model"))
    assert PlacementMap.from_mesh(mesh, 12).n_hosts == 6


def test_placement_validation():
    with pytest.raises(ValueError):
        PlacementMap(np.asarray([0, 5]), np.zeros((2, 0), np.int64), 2)
    with pytest.raises(ValueError):                 # replica == primary
        PlacementMap(np.asarray([0, 1]), np.asarray([[0], [0]]), 2)
    with pytest.raises(ValueError):
        PlacementMap.blocked(4, 0)


# ----------------------------------------------------------------------
# HostGroupExecutor: gather parity + accounting
# ----------------------------------------------------------------------
def test_map_shards_matches_single_executor():
    pm = PlacementMap.blocked(12, 3, n_replicas=1)
    with HostGroupExecutor(pm, workers_per_host=2) as hg, \
            ShardTaskExecutor(workers=2) as single:
        corpus = _FakeCorpus(12)
        got = hg.map_shards(corpus, range(12), lambda s: s.shard_id * 3)
        want = single.map_shards(corpus, range(12), lambda s: s.shard_id * 3)
    assert got == want
    assert hg.stats["jobs"] == 1 and hg.stats["host_failures"] == 0


def test_shared_scan_splits_by_residency_and_gathers():
    pm = PlacementMap.blocked(8, 2, n_replicas=1)
    plan = [[0, 1, 6], [1, 6, 7], [2]]
    fns = [lambda s, q=q: (q, s.shard_id) for q in range(3)]
    with HostGroupExecutor(pm, workers_per_host=1) as hg, \
            ShardTaskExecutor(workers=2) as single:
        got = hg.map_shard_batch(_FakeCorpus(8), plan, fns)
        want = single.map_shard_batch(_FakeCorpus(8), plan, fns)
        assert got == want
        # per-host scans == the union plan's residency split, not the
        # sum of per-query plan sizes (5 union shards, 7 plan entries)
        union = sorted(invert_plan(plan))
        assert union == [0, 1, 2, 6, 7]
        assert hg.residency_split(plan) == {0: 3, 1: 2}
        assert hg.stats["scans_per_host"] == [3, 2]
        assert hg.last_job["tasks"] == 5.0 and hg.last_job["hosts"] == 2.0


def test_host_failure_requeues_on_replica():
    pm = PlacementMap.blocked(10, 2, n_replicas=1)
    downed = []

    def host_fault(host, shard_ids):
        if host == 0 and not downed:
            downed.append(list(shard_ids))
            raise RuntimeError("injected host fault")

    with HostGroupExecutor(pm, workers_per_host=1,
                           host_fault_hook=host_fault) as hg:
        out = hg.map_shards(_FakeCorpus(10), range(10),
                            lambda s: s.shard_id + 100)
    assert out == {i: i + 100 for i in range(10)}
    assert downed == [[0, 1, 2, 3, 4]]          # host 0's whole group died
    assert hg.stats["host_failures"] == 1
    assert hg.stats["requeued_shards"] == 5
    # every scan landed on the surviving replica host
    assert hg.stats["scans_per_host"] == [0, 10]


def test_host_failure_without_replica_raises():
    pm = PlacementMap.blocked(6, 2, n_replicas=0)

    def host_fault(host, shard_ids):
        if host == 1:
            raise RuntimeError("host 1 is gone")

    with HostGroupExecutor(pm, workers_per_host=1,
                           host_fault_hook=host_fault) as hg:
        with pytest.raises(HostFailure) as exc:
            hg.map_shards(_FakeCorpus(6), range(6), lambda s: s.shard_id)
    # the real host exception is chained, not swallowed — a bug in a
    # query fn must not masquerade as pure infrastructure loss
    assert isinstance(exc.value.__cause__, RuntimeError)
    assert "host 1 is gone" in str(exc.value.__cause__)


def test_task_fault_hook_forwards_to_host_executors():
    """Shard-granularity faults stay the per-host executor's business:
    retries absorb them without tripping host failover."""
    fails = {3: 1}

    def hook(sid, attempt):
        if fails.get(sid, 0) >= attempt:
            raise RuntimeError("transient task fault")

    pm = PlacementMap.blocked(8, 2, n_replicas=1)
    with HostGroupExecutor(pm, workers_per_host=2, max_retries=2,
                           fault_hook=hook) as hg:
        out = hg.map_shards(_FakeCorpus(8), range(8), lambda s: s.shard_id)
    assert out == {i: i for i in range(8)}
    assert hg.stats["host_failures"] == 0
    assert sum(ex.stats["retries"] for ex in hg.hosts.values()) >= 1


def test_close_is_idempotent():
    hg = HostGroupExecutor(PlacementMap.blocked(4, 2), workers_per_host=1)
    hg.map_shards(_FakeCorpus(4), range(4), lambda s: 1)
    hg.close()
    hg.close()
    assert all(ex._pool is None for ex in hg.hosts.values())


# ----------------------------------------------------------------------
# end-to-end: QueryBatch through a 2-host group, bit-for-bit vs single
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


@pytest.mark.parametrize("rate", [0.4, 1.0])
def test_query_batch_host_group_matches_single_executor(
        small_corpus, built_index, rate):
    queries = _mixed_queries()
    pm = PlacementMap.blocked(small_corpus.n_shards, 2, n_replicas=1)
    with ShardTaskExecutor(workers=2) as single, \
            HostGroupExecutor(pm, workers_per_host=1) as hg:
        want = QueryBatch(small_corpus, built_index, executor=single
                          ).execute(queries, rate,
                                    rng=np.random.default_rng(42))
        engine = QueryBatch(small_corpus, built_index, executor=hg)
        got = engine.execute(queries, rate, rng=np.random.default_rng(42))
        # the gathered reduce is bit-for-bit the single-executor reduce
        _assert_results_identical(got, want)
        # per-host scans match the residency split of the executed plan
        split = hg.residency_split(engine.last_plan)
        observed = {h: c for h, c in
                    enumerate(hg.stats["scans_per_host"]) if c}
        assert observed == split


def test_query_batch_survives_host_fault_bit_for_bit(small_corpus,
                                                     built_index):
    """The satellite requirement: a 2-host placement with an injected
    host fault re-executes that host's shards on the replica and the
    cross-host gathered reduce still matches the single-executor path
    bit-for-bit, for all three query types."""
    queries = _mixed_queries()
    with ShardTaskExecutor(workers=2) as single:
        want = QueryBatch(small_corpus, built_index, executor=single
                          ).execute(queries, 0.5,
                                    rng=np.random.default_rng(7))

    downed = []

    def host_fault(host, shard_ids):
        if host == 1 and not downed:
            downed.append(host)
            raise RuntimeError("host 1 down")

    pm = PlacementMap.blocked(small_corpus.n_shards, 2, n_replicas=1)
    with HostGroupExecutor(pm, workers_per_host=1,
                           host_fault_hook=host_fault) as hg:
        got = QueryBatch(small_corpus, built_index, executor=hg
                         ).execute(queries, 0.5,
                                   rng=np.random.default_rng(7))
    assert downed == [1]                        # the fault actually fired
    assert hg.stats["host_failures"] == 1
    assert hg.stats["requeued_shards"] > 0
    assert hg.stats["scans_per_host"][1] == 0   # replica took every scan
    _assert_results_identical(got, want)
