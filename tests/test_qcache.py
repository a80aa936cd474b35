"""Semantic query cache: exact-hit bit-for-bit parity, near-hit
estimator unbiasedness, placement-epoch fencing through FleetManager,
LRU/TTL eviction, and the fidelity fences (degraded / budgeted /
pressured answers never cached)."""
import json

import numpy as np
import pytest

from repro.core.queries import BatchQuery, QueryBatch, parse_boolean
from repro.runtime import (
    FleetManager,
    HostGroupExecutor,
    PlacementMap,
    WindowController,
)
from repro.runtime.budget import QueryBudget, RatePlanner
from repro.runtime.generation import Generation
from repro.runtime.qcache import (
    QueryCacheConfig,
    SemanticQueryCache,
    query_key,
    sampler_class,
)

RATE = 0.4


def _queries():
    return [BatchQuery.count([5]),
            BatchQuery.boolean(parse_boolean([3, "and", 8])),
            BatchQuery.ranked([3, 8, 11], k=5),
            BatchQuery.count([2, 7])]


def _cfg(**kw):
    kw.setdefault("max_entries", 64)
    kw.setdefault("ttl_s", 3600.0)
    kw.setdefault("hamming_radius", 0)
    return QueryCacheConfig(**kw)


def _strip_elapsed(res):
    return res._replace(elapsed_s=0.0)


def _same_result(a, b):
    return repr(_strip_elapsed(a)) == repr(_strip_elapsed(b))


# ----------------------------------------------------------------------
# unit: keys, config, LRU / TTL / epoch mechanics (no engine)
# ----------------------------------------------------------------------
def _sig(*bits):
    """A 128-bit packed signature with the given bit positions set."""
    words = np.zeros(4, np.uint32)
    for b in bits:
        words[b // 32] |= np.uint32(1) << np.uint32(b % 32)
    return words


def test_query_key_distinguishes_kinds_and_structure():
    keys = {query_key(q) for q in _queries()}
    assert len(keys) == 4
    # same words, different k -> different identity
    assert (query_key(BatchQuery.ranked([1, 2], k=5))
            != query_key(BatchQuery.ranked([1, 2], k=7)))
    # AND vs OR over the same words -> different identity
    assert (query_key(BatchQuery.boolean(parse_boolean([1, "and", 2])))
            != query_key(BatchQuery.boolean(parse_boolean([1, "or", 2]))))
    assert sampler_class("count") == "hh"
    assert sampler_class("bool") == sampler_class("ranked") == "distinct"


def test_config_validation():
    with pytest.raises(ValueError):
        QueryCacheConfig(max_entries=0)
    with pytest.raises(ValueError):
        QueryCacheConfig(ttl_s=0.0)
    with pytest.raises(ValueError):
        QueryCacheConfig(hamming_radius=-1)


def test_exact_hit_requires_key_and_rate():
    c = SemanticQueryCache(_cfg())
    k = ("count", (5,))
    c.insert(_sig(3), k, "hh", 0.4, probs=None, sample="S", plan="P",
             result="R", epoch=0)
    kind, e = c.lookup(_sig(3), k, "hh", 0.4, 0)
    assert kind == "hit" and e.result == "R"
    # different rate -> miss even with an identical signature
    assert c.lookup(_sig(3), k, "hh", 0.5, 0)[0] == "miss"
    # different query at the same signature -> NOT a full hit: it may
    # only borrow the plan (a radius-0 "near"), never the result
    kind, e = c.lookup(_sig(3), ("count", (6,)), "hh", 0.4, 0)
    assert kind == "near" and e.plan == "P"
    assert c.stats == dict(hits=1, near_hits=1, misses=1, bypassed=0,
                           insertions=1, evictions=0, expired=0,
                           stale_epoch=0)


def test_near_hit_within_radius_same_class_same_rate():
    c = SemanticQueryCache(_cfg(hamming_radius=2))
    c.insert(_sig(3, 64), ("count", (5,)), "hh", 0.4, probs=None,
             sample="S", plan="P", result="R", epoch=0)
    # 1 bit away, same class/rate: borrows the plan
    kind, e = c.lookup(_sig(3, 64, 99), ("count", (6,)), "hh", 0.4, 0)
    assert kind == "near" and e.plan == "P"
    # 3 bits away: outside the radius
    assert c.lookup(_sig(3, 64, 97, 98, 99), ("count", (6,)),
                    "hh", 0.4, 0)[0] == "miss"
    # same signature, wrong sampler class or rate: never near
    assert c.lookup(_sig(3, 64), ("ranked", (5,), 10), "distinct",
                    0.4, 0)[0] == "miss"
    assert c.lookup(_sig(3, 64), ("count", (6,)), "hh", 0.3, 0)[0] == "miss"


def test_lru_eviction_bound():
    c = SemanticQueryCache(_cfg(max_entries=3))
    for i in range(5):
        c.insert(_sig(i), ("count", (i,)), "hh", 0.4, probs=None,
                 sample=None, plan=None, result=i, epoch=0)
    assert len(c) == 3 and c.stats["evictions"] == 2
    # oldest two are gone, newest three live
    assert c.lookup(_sig(0), ("count", (0,)), "hh", 0.4, 0)[0] == "miss"
    assert c.lookup(_sig(4), ("count", (4,)), "hh", 0.4, 0)[0] == "hit"
    # a hit refreshes recency: 2 survives the next two insertions
    c.lookup(_sig(2), ("count", (2,)), "hh", 0.4, 0)
    for i in range(5, 7):
        c.insert(_sig(i), ("count", (i,)), "hh", 0.4, probs=None,
                 sample=None, plan=None, result=i, epoch=0)
    assert c.lookup(_sig(2), ("count", (2,)), "hh", 0.4, 0)[0] == "hit"


def test_ttl_expiry_with_injected_clock():
    t = [0.0]
    c = SemanticQueryCache(_cfg(ttl_s=10.0), clock=lambda: t[0])
    c.insert(_sig(1), ("count", (1,)), "hh", 0.4, probs=None,
             sample=None, plan=None, result="R", epoch=0)
    t[0] = 9.0
    assert c.lookup(_sig(1), ("count", (1,)), "hh", 0.4, 0)[0] == "hit"
    t[0] = 11.0
    assert c.lookup(_sig(1), ("count", (1,)), "hh", 0.4, 0)[0] == "miss"
    assert c.stats["expired"] == 1 and len(c) == 0


def test_epoch_fences_entries():
    c = SemanticQueryCache(_cfg())
    key = (_sig(1), ("count", (1,)), "hh", 0.4)
    born = Generation(placement=3, content=0)
    # a placement bump and a content bump each fence the entry
    for probe in (Generation(4, 0), Generation(3, 1)):
        c.insert(*key, probs=None, sample=None, plan=None, result="R",
                 epoch=born)
        assert c.lookup(*key, probe)[0] == "miss"
    assert c.stats["stale_epoch"] == 2 and len(c) == 0
    c.insert(*key, probs=None, sample=None, plan=None, result="R",
             epoch=born)
    assert c.lookup(*key, Generation(3, 0))[0] == "hit"


def test_purge_and_record():
    t = [0.0]
    c = SemanticQueryCache(_cfg(ttl_s=10.0), clock=lambda: t[0])
    c.insert(_sig(1), ("count", (1,)), "hh", 0.4, probs=None,
             sample=None, plan=None, result="R", epoch=0)
    c.insert(_sig(2), ("count", (2,)), "hh", 0.4, probs=None,
             sample=None, plan=None, result="R", epoch=1)
    t[0] = 11.0
    t2 = [0.0]
    c._clock = lambda: t2[0]  # keep the epoch-1 entry fresh
    assert c.purge(epoch=1) == 1          # the epoch-0 entry
    assert len(c) == 1
    rec = json.loads(json.dumps(c.record()))
    assert rec["size"] == 1 and rec["stale_epoch"] == 1


# ----------------------------------------------------------------------
# engine integration: parity, rng independence, near-hit statistics
# ----------------------------------------------------------------------
def test_cold_cache_is_bit_for_bit_uncached(small_corpus, built_index):
    qs = _queries()
    plain = QueryBatch(small_corpus, built_index)
    cached = QueryBatch(small_corpus, built_index,
                        cache=SemanticQueryCache(_cfg()))
    want = plain.execute(qs, RATE, rng=np.random.default_rng(7))
    got = cached.execute(qs, RATE, rng=np.random.default_rng(7))
    assert all(_same_result(g, w) for g, w in zip(got, want))
    assert cached.cache.stats["misses"] == len(qs)
    assert cached.cache.stats["hits"] == 0


def test_exact_hits_bit_for_bit_and_rng_independent(small_corpus,
                                                    built_index):
    qs = _queries()
    cache = SemanticQueryCache(_cfg())
    eng = QueryBatch(small_corpus, built_index, cache=cache)
    first = eng.execute(qs, RATE, rng=np.random.default_rng(7))
    # a DIFFERENT generator: hits consume no rng, so the results must
    # still be the memoized ones, verbatim
    again = eng.execute(qs, RATE, rng=np.random.default_rng(12345))
    assert cache.stats["hits"] == len(qs)
    assert all(_same_result(a, f) for a, f in zip(again, first))
    # the executed plan for a hit is empty — nothing was scanned
    assert all(len(p) == 0 for p in eng.last_report.plan)
    assert eng.last_report.cache == dict(hits=4, near_hits=0, misses=0,
                                         bypassed=0)


def test_mixed_batch_misses_draw_as_if_alone(small_corpus, built_index):
    """Hits consume no rng: the remaining misses must draw exactly what
    they would draw in a batch of their own."""
    qs = _queries()
    cache = SemanticQueryCache(_cfg())
    eng = QueryBatch(small_corpus, built_index, cache=cache)
    eng.execute(qs[:2], RATE, rng=np.random.default_rng(7))  # populate 2
    mixed = eng.execute(qs, RATE, rng=np.random.default_rng(9))
    alone = QueryBatch(small_corpus, built_index).execute(
        qs[2:], RATE, rng=np.random.default_rng(9))
    assert cache.stats["hits"] == 2
    assert all(_same_result(m, a) for m, a in zip(mixed[2:], alone))


def test_near_hit_borrows_plan_and_stays_unbiased(small_corpus,
                                                  built_index):
    """Hansen-Hurwitz is unbiased for ANY full-support sampling
    distribution, so a count served off a *neighbor's* cached plan must
    agree with the exact answer in expectation.  Radius = all bits so
    the neighbor always qualifies."""
    qa, qb = BatchQuery.count([5]), BatchQuery.count([9])
    exact = QueryBatch(small_corpus, built_index).execute(
        [qb], 1.0)[0].estimate.value
    vals = []
    for seed in range(250):
        cache = SemanticQueryCache(_cfg(hamming_radius=built_index.bits))
        eng = QueryBatch(small_corpus, built_index, cache=cache)
        eng.execute([qa], RATE, rng=np.random.default_rng(seed))
        res = eng.execute([qb], RATE,
                          rng=np.random.default_rng(seed + 10_000))[0]
        assert cache.stats["near_hits"] == 1, "neighbor did not qualify"
        # the borrowed plan executed a real scan (not a memoized result)
        assert len(eng.last_report.plan[0]) > 0
        vals.append(res.estimate.value)
    mean = float(np.mean(vals))
    sem = float(np.std(vals, ddof=1) / np.sqrt(len(vals)))
    assert abs(mean - exact) <= 4.0 * sem + 1e-9, (
        f"near-hit estimator biased: mean {mean:.2f} vs exact "
        f"{exact:.2f} (sem {sem:.2f})")


def test_near_hit_inserts_own_entry(small_corpus, built_index):
    """A near-hit runs a real reduce, so its full-fidelity result is
    cacheable: the next identical ask is an exact hit."""
    qa, qb = BatchQuery.count([5]), BatchQuery.count([9])
    cache = SemanticQueryCache(_cfg(hamming_radius=built_index.bits))
    eng = QueryBatch(small_corpus, built_index, cache=cache)
    eng.execute([qa], RATE, rng=np.random.default_rng(0))
    eng.execute([qb], RATE, rng=np.random.default_rng(1))
    assert cache.stats["near_hits"] == 1
    res = eng.execute([qb], RATE, rng=np.random.default_rng(2))[0]
    assert cache.stats["hits"] == 1
    assert res.estimate is not None


# ----------------------------------------------------------------------
# placement-epoch fencing through the fleet
# ----------------------------------------------------------------------
def _fleet_stack(small_corpus, built_index, n_replicas=1, **hg_kw):
    hg = HostGroupExecutor(
        PlacementMap.blocked(small_corpus.n_shards, 2,
                             n_replicas=n_replicas),
        workers_per_host=1, **hg_kw)
    cache = SemanticQueryCache(_cfg())
    eng = QueryBatch(small_corpus, built_index, executor=hg, cache=cache)
    return hg, FleetManager(hg, warm_fn=lambda sid, src, dst: None), \
        cache, eng


@pytest.mark.parametrize("swap", ["join", "drain", "crash"])
def test_fleet_swap_invalidates_cached_plans(small_corpus, built_index,
                                             swap):
    qs = _queries()
    hg, fleet, cache, eng = _fleet_stack(small_corpus, built_index)
    with hg:
        eng.execute(qs, RATE, rng=np.random.default_rng(7))   # populate
        eng.execute(qs, RATE, rng=np.random.default_rng(8))
        assert cache.stats["hits"] == len(qs)                 # warm
        if swap == "join":
            fleet.join(2)
        elif swap == "drain":
            fleet.drain(1)
        else:
            fleet.crash(1)
        got = eng.execute(qs, RATE, rng=np.random.default_rng(9))
        # zero hits crossed the generation swap; every entry dropped
        assert cache.stats["hits"] == len(qs)
        assert cache.stats["stale_epoch"] == len(qs)
        # and the re-served results match a plain engine on the same
        # post-swap topology under the same seeds
        want = QueryBatch(small_corpus, built_index, executor=hg).execute(
            qs, RATE, rng=np.random.default_rng(9))
        assert all(_same_result(g, w) for g, w in zip(got, want))
        # repopulated at the new epoch: warm again
        eng.execute(qs, RATE, rng=np.random.default_rng(10))
        assert cache.stats["hits"] == 2 * len(qs)


# ----------------------------------------------------------------------
# fidelity fences: degraded / budgeted / pressured never cached
# ----------------------------------------------------------------------
def test_degraded_results_never_cached(small_corpus, built_index):
    hg, fleet, cache, eng = _fleet_stack(small_corpus, built_index,
                                         n_replicas=0, allow_partial=True)
    with hg:
        fleet.crash(1)      # no replicas: host 1's shards are orphaned
        res = eng.execute([BatchQuery.count([5])], 1.0,
                          rng=np.random.default_rng(0))[0]
        assert res.lost_shards > 0
        assert cache.stats["insertions"] == 0 and len(cache) == 0


def test_budgeted_queries_bypass_cache(small_corpus, built_index):
    cache = SemanticQueryCache(_cfg())
    eng = QueryBatch(small_corpus, built_index,
                     planner=RatePlanner(small_corpus.n_shards),
                     cache=cache)
    budgeted = BatchQuery.count([5], budget=QueryBudget(max_rel_error=0.5))
    plain = BatchQuery.count([9])
    for seed in (0, 1):
        eng.execute([budgeted, plain], RATE,
                    rng=np.random.default_rng(seed))
    # the budgeted query never probed nor populated; the plain one hit
    assert cache.stats["bypassed"] == 2
    assert cache.stats["insertions"] == 1
    assert cache.stats["hits"] == 1
    assert eng.last_report.cache["bypassed"] == 1


def test_pressure_bypasses_cache_both_directions(small_corpus,
                                                 built_index):
    cache = SemanticQueryCache(_cfg())
    eng = QueryBatch(small_corpus, built_index,
                     planner=RatePlanner(small_corpus.n_shards),
                     cache=cache)
    qs = [BatchQuery.count([5])]
    eng.execute(qs, RATE, rng=np.random.default_rng(0))   # populate
    eng.execute(qs, RATE, rng=np.random.default_rng(1), pressure=0.7)
    # the degraded batch neither read the warm entry nor replaced it
    assert cache.stats["hits"] == 0 and cache.stats["bypassed"] == 1
    assert cache.stats["insertions"] == 1


# ----------------------------------------------------------------------
# controller: cached queries stay out of the batch cost fit
# ----------------------------------------------------------------------
def test_controller_excludes_cached_from_cost_fit():
    c = WindowController()
    c.observe_batch(4, 0.01, cached=4)        # all-cached: dropped
    assert c._n_batches == 0
    c.observe_batch(4, 0.01, cached=2)        # fits as a 2-query batch
    assert c._n_batches == 1
