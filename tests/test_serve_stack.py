"""Serving facade (launch/serve_stack) + ExecutionReport: one-call
construction parity vs hand-built stacks, layer wiring (topology /
planner / cache / window / fleet), config validation, and the typed
per-batch report."""
import dataclasses
import json

import numpy as np
import pytest

from repro.core.queries import (
    BatchQuery,
    ExecutionReport,
    QueryBatch,
    parse_boolean,
)
from repro.launch import ServeConfig, ServingStack, build_serving_stack
from repro.runtime import (
    BatchWindow,
    FleetManager,
    HostGroupExecutor,
    ShardTaskExecutor,
    WindowController,
)
from repro.runtime.budget import RatePlanner
from repro.runtime.qcache import QueryCacheConfig, SemanticQueryCache

QS = [BatchQuery.count([5]),
      BatchQuery.boolean(parse_boolean([3, "and", 8])),
      BatchQuery.ranked([3, 8, 11], k=5)]


def _same(a, b):
    return repr(a._replace(elapsed_s=0)) == repr(b._replace(elapsed_s=0))


# ----------------------------------------------------------------------
# construction + parity
# ----------------------------------------------------------------------
def test_default_stack_matches_hand_built_engine(small_corpus,
                                                 built_index):
    with build_serving_stack(small_corpus, built_index) as stack:
        assert isinstance(stack.executor, ShardTaskExecutor)
        assert stack.window is None and stack.planner is None
        assert stack.cache is None and stack.fleet is None
        got = stack.engine.execute(QS, 0.4, rng=np.random.default_rng(3))
    with ShardTaskExecutor(workers=2) as ex:
        want = QueryBatch(small_corpus, built_index, executor=ex).execute(
            QS, 0.4, rng=np.random.default_rng(3))
    assert all(_same(g, w) for g, w in zip(got, want))


def test_config_and_kwarg_overrides_compose(small_corpus, built_index):
    cfg = ServeConfig(rate=0.3, workers=1)
    with build_serving_stack(small_corpus, built_index, cfg,
                             ci=True) as stack:
        assert stack.config.rate == 0.3       # from the config
        assert stack.config.ci is True        # from the override
        assert stack.engine.ci is True
    assert cfg.ci is False                    # original untouched


def test_host_group_topology_and_fleet(small_corpus, built_index):
    with build_serving_stack(small_corpus, built_index, hosts=2,
                             replicas=1, fleet=True) as stack:
        assert isinstance(stack.executor, HostGroupExecutor)
        assert isinstance(stack.fleet, FleetManager)
        got = stack.engine.execute(QS, 0.4, rng=np.random.default_rng(3))
        assert len(got) == len(QS)
        # the fleet drives the SAME executor the engine serves from
        stack.fleet.drain(1)
        assert stack.executor.clock.current().placement == 1


def test_cache_wiring_serves_hits(small_corpus, built_index):
    with build_serving_stack(
            small_corpus, built_index, cache=True,
            cache_config=QueryCacheConfig(max_entries=8, ttl_s=3600.0,
                                          hamming_radius=0)) as stack:
        assert isinstance(stack.cache, SemanticQueryCache)
        assert stack.engine.cache is stack.cache
        first = stack.engine.execute(QS, 0.4,
                                     rng=np.random.default_rng(3))
        again = stack.engine.execute(QS, 0.4,
                                     rng=np.random.default_rng(99))
        assert stack.cache.stats["hits"] == len(QS)
        assert all(_same(a, f) for a, f in zip(again, first))


def test_planner_and_window_wiring(small_corpus, built_index):
    with build_serving_stack(small_corpus, built_index, planner=True,
                             ci=True, window=True, max_batch=4,
                             max_delay_s=0.001) as stack:
        assert isinstance(stack.planner, RatePlanner)
        assert isinstance(stack.controller, WindowController)
        assert isinstance(stack.window, BatchWindow)
        assert stack.window.controller is stack.controller
        assert stack.engine.accepts_pressure
        res = stack.window.submit(QS[0]).result(timeout=30)
        assert res.estimate is not None
    # context-manager exit closed the window: further submits refuse
    with pytest.raises(RuntimeError):
        stack.window.submit(QS[0])


def test_window_static_mode_has_no_controller(small_corpus, built_index):
    with build_serving_stack(small_corpus, built_index, window=True,
                             adaptive=False) as stack:
        assert stack.window is not None and stack.controller is None


def test_config_validation_errors(small_corpus, built_index):
    with pytest.raises(ValueError):
        ServeConfig(balanced=True)            # needs hosts >= 2
    with pytest.raises(ValueError):
        ServeConfig(fleet=True)
    with pytest.raises(ValueError):
        ServeConfig(host_fault_hook=lambda h, s: None)
    with pytest.raises(ValueError):
        ServeConfig(workers=0)
    with pytest.raises(ValueError):
        ServeConfig(hosts=-1)
    with pytest.raises(TypeError):            # unknown knob is a typo
        build_serving_stack(small_corpus, built_index, no_such_knob=1)


# ----------------------------------------------------------------------
# ExecutionReport: the typed per-batch record
# ----------------------------------------------------------------------
def test_execution_report_contents_and_json(small_corpus, built_index):
    eng = QueryBatch(small_corpus, built_index)
    assert eng.last_report is None
    eng.execute(QS, 0.4, rng=np.random.default_rng(3))
    r = eng.last_report
    assert isinstance(r, ExecutionReport)
    assert r.n_queries == len(QS) and r.rate == 0.4
    assert len(r.rates) == len(r.plan) == len(QS)
    assert all(isinstance(p, np.ndarray) for p in r.plan)
    assert r.balance is None and r.budget is None
    assert r.degraded is None and r.cache is None
    rec = json.loads(json.dumps(r.record()))
    assert rec["n_queries"] == len(QS)
    assert all(isinstance(s, int) for p in rec["plan"] for s in p)


def test_report_absent_before_first_execute_and_frozen(small_corpus,
                                                        built_index):
    eng = QueryBatch(small_corpus, built_index)
    assert eng.last_report is None
    eng.execute(QS, 0.4, rng=np.random.default_rng(3))
    r = eng.last_report
    assert r is not None
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.n_queries = 0
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.plan = []


def test_report_is_per_batch(small_corpus, built_index):
    eng = QueryBatch(small_corpus, built_index)
    eng.execute(QS, 0.4, rng=np.random.default_rng(3))
    first = eng.last_report
    eng.execute(QS[:1], 0.6, rng=np.random.default_rng(4))
    assert eng.last_report is not first
    assert eng.last_report.n_queries == 1
    assert eng.last_report.rate == 0.6


def test_stack_dataclass_shape(small_corpus, built_index):
    stack = build_serving_stack(small_corpus, built_index)
    try:
        assert isinstance(stack, ServingStack)
        assert stack.corpus is small_corpus
        assert stack.index is built_index
        assert isinstance(stack.config, ServeConfig)
    finally:
        stack.close()
        stack.close()      # idempotent
