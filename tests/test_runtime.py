"""Fault-tolerance runtime: executor retries, speculation, checkpoints,
optimizer state compression, pipeline."""
import os
import threading
import time

import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.runtime.executor import ShardTaskError, ShardTaskExecutor


class _FakeShard:
    def __init__(self, i):
        self.shard_id = i


class _FakeCorpus:
    def __init__(self, n):
        self.shards = [_FakeShard(i) for i in range(n)]


def test_executor_basic():
    ex = ShardTaskExecutor(workers=4)
    out = ex.map_shards(_FakeCorpus(10), range(10), lambda s: s.shard_id * 2)
    assert out == {i: i * 2 for i in range(10)}


def test_executor_retries_transient_failures():
    fails = {3: 2, 7: 1}   # shard -> number of failures before success

    def hook(sid, attempt):
        if fails.get(sid, 0) >= attempt:
            raise RuntimeError(f"injected fault on {sid}")

    ex = ShardTaskExecutor(workers=4, max_retries=3, fault_hook=hook)
    out = ex.map_shards(_FakeCorpus(10), range(10), lambda s: s.shard_id)
    assert out == {i: i for i in range(10)}
    assert ex.stats["retries"] >= 3


def test_executor_permanent_failure_raises():
    def hook(sid, attempt):
        if sid == 5:
            raise RuntimeError("dead shard")

    ex = ShardTaskExecutor(workers=2, max_retries=1, fault_hook=hook)
    with pytest.raises(ShardTaskError):
        ex.map_shards(_FakeCorpus(8), range(8), lambda s: s.shard_id)


def test_executor_straggler_speculation():
    slow_once = {9}
    seen = {}
    lock = threading.Lock()

    def work(shard):
        with lock:
            n = seen.get(shard.shard_id, 0)
            seen[shard.shard_id] = n + 1
        if shard.shard_id in slow_once and n == 0:
            time.sleep(1.5)    # straggler on first attempt
        else:
            time.sleep(0.01)
        return shard.shard_id

    ex = ShardTaskExecutor(workers=4, straggler_factor=3.0,
                           min_completed_for_speculation=4)
    out = ex.map_shards(_FakeCorpus(10), range(10), work)
    assert out[9] == 9
    assert ex.stats["speculative"] >= 1
    # the duplicate attempt actually ran (n >= 2 for the straggler)
    assert seen[9] >= 2


def test_executor_elastic_resize():
    ex = ShardTaskExecutor(workers=2)
    ex.resize(8)
    out = ex.map_shards(_FakeCorpus(20), range(20), lambda s: 1)
    assert len(out) == 20


def test_executor_failure_drains_in_flight_tasks():
    """Regression: ShardTaskError must not escape while sibling tasks
    are still running on the shared warm pool (the old per-job pool
    guaranteed quiescence via its `with` shutdown)."""
    running = {"n": 0}
    lock = threading.Lock()

    def work(shard):
        if shard.shard_id == 5:
            raise RuntimeError("dead shard")
        with lock:
            running["n"] += 1
        time.sleep(0.15)
        with lock:
            running["n"] -= 1
        return shard.shard_id

    ex = ShardTaskExecutor(workers=4, max_retries=0)
    with pytest.raises(ShardTaskError):
        ex.map_shards(_FakeCorpus(8), range(8), work)
    assert running["n"] == 0          # no zombie tasks past the raise
    ex.close()


def test_executor_warm_pool_persists_across_jobs():
    ex = ShardTaskExecutor(workers=3, gil_floor_s=0.0)   # pinned to the pool
    ex.map_shards(_FakeCorpus(10), range(10), lambda s: s.shard_id)
    pool = ex._pool
    assert pool is not None
    ex.map_shards(_FakeCorpus(6), range(6), lambda s: s.shard_id)
    assert ex._pool is pool                   # no per-job construction
    assert ex.stats["pool_rebuilds"] == 1
    assert ex.stats["jobs"] == 2
    ex.resize(5)                              # swap happens on next job
    assert ex._pool is pool
    ex.map_shards(_FakeCorpus(4), range(4), lambda s: 1)
    assert ex._pool is not pool and ex._pool_size == 5
    assert ex.stats["pool_rebuilds"] == 2
    ex.close()
    assert ex._pool is None
    ex.close()                                # idempotent


def test_executor_context_manager_closes_pool():
    with ShardTaskExecutor(workers=2, gil_floor_s=0.0) as ex:
        ex.map_shards(_FakeCorpus(4), range(4), lambda s: 1)
        assert ex._pool is not None
    assert ex._pool is None


# ----------------------------------------------------------------------
# the sweep (calling thread, the default) and the pool (gil_floor_s=0.0)
# keep one per-shard contract
# ----------------------------------------------------------------------
MODES = {"sweep": {}, "pooled": {"gil_floor_s": 0.0}}


def _executor(mode, **kw):
    return ShardTaskExecutor(workers=4, **MODES[mode], **kw)


@pytest.mark.parametrize("mode", list(MODES))
def test_executor_modes_give_equal_results(mode):
    with _executor(mode) as ex:
        out = ex.map_shards(_FakeCorpus(16), range(16),
                            lambda s: (s.shard_id, s.shard_id ** 2))
        assert out == {i: (i, i * i) for i in range(16)}
        assert ex.last_job["sweep"] == float(mode == "sweep")
        assert ex.last_job["tasks"] == 16.0
        assert ex.last_job["wall_s"] > 0.0
        assert ex.last_job["lost_shards"] == 0.0
        assert ex.stats["jobs"] == 1


@pytest.mark.parametrize("mode", list(MODES))
def test_executor_hooks_fire_per_shard_and_attempt(mode):
    faults, tasks, jobs = [], [], []
    lock = threading.Lock()

    def fault_hook(sid, attempt):
        with lock:
            faults.append((sid, attempt))
        if sid == 3 and attempt == 1:
            raise RuntimeError("injected")

    def task_hook(sid, attempt, job):
        with lock:
            tasks.append((sid, attempt, job))

    with _executor(mode, fault_hook=fault_hook, task_hook=task_hook,
                   job_hook=jobs.append, max_retries=1) as ex:
        ex.map_shards(_FakeCorpus(6), range(6), lambda s: s.shard_id)
        ex.map_shards(_FakeCorpus(6), [5], lambda s: s.shard_id)
    assert jobs == [0, 1]
    assert sorted(faults) == sorted(
        [(s, 1) for s in range(6)] + [(3, 2), (5, 1)])
    # the fault hook raised before the task hook on shard 3's first try
    assert sorted(tasks) == sorted(
        [(s, 1, 0) for s in range(6) if s != 3] + [(3, 2, 0), (5, 1, 1)])


@pytest.mark.parametrize("mode", list(MODES))
def test_executor_transient_faults_retry_with_backoff(mode):
    def hook(sid, attempt):
        if sid == 2 and attempt <= 2:
            raise RuntimeError("transient")

    with _executor(mode, fault_hook=hook, max_retries=2,
                   retry_backoff_s=0.03, retry_backoff_cap_s=1.0) as ex:
        t0 = time.perf_counter()
        out = ex.map_shards(_FakeCorpus(6), range(6), lambda s: s.shard_id)
        dt = time.perf_counter() - t0
    assert out == {i: i for i in range(6)}
    assert ex.stats["retries"] == 2
    assert dt >= 0.08            # waited 0.03 then 0.06 before retrying


@pytest.mark.parametrize("mode", list(MODES))
def test_executor_permanent_failure_raises_shard_task_error(mode):
    def hook(sid, attempt):
        if sid == 4:
            raise RuntimeError("dead shard")

    with _executor(mode, fault_hook=hook, max_retries=1) as ex:
        with pytest.raises(ShardTaskError, match="shard 4 failed"):
            ex.map_shards(_FakeCorpus(8), range(8), lambda s: s.shard_id)
        assert ex.stats["retries"] == 1
        assert ex.stats["jobs"] == 0 and ex.last_job is None


@pytest.mark.parametrize("mode", list(MODES))
def test_executor_allow_partial_records_lost_shards(mode):
    def hook(sid, attempt):
        if sid == 5:
            raise RuntimeError("dead shard")

    with _executor(mode, fault_hook=hook, max_retries=1,
                   allow_partial=True) as ex:
        out = ex.map_shards(_FakeCorpus(8), range(8), lambda s: s.shard_id)
        assert out == {i: i for i in range(8) if i != 5}
        assert ex.stats["lost_shards"] == 1
        assert ex.last_job["lost_shards"] == 1.0
        assert ex.last_job["sweep"] == float(mode == "sweep")


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("partial", [True, False])
def test_executor_deadline_abandons_stalled_task_on_pool(mode, partial):
    """A job with a deadline never sweeps: only the pool can abandon a
    stalled task when the deadline expires, so both modes run it there."""
    def work(shard):
        if shard.shard_id == 2:
            time.sleep(0.5)      # stalls far past the 0.1 s deadline
        return shard.shard_id

    with _executor(mode, job_deadline_s=0.1, allow_partial=partial) as ex:
        t0 = time.perf_counter()
        if not partial:
            with pytest.raises(ShardTaskError, match="deadline"):
                ex.map_shards(_FakeCorpus(10), range(10), work)
        else:
            out = ex.map_shards(_FakeCorpus(10), range(10), work)
        # abandoned at the deadline instead of holding the job open
        assert time.perf_counter() - t0 < 0.4
        assert ex.stats["sweep_jobs"] == 0
        assert ex._pool is not None
    if not partial:
        return
    assert out == {i: i for i in range(10) if i != 2}
    assert ex.last_job["sweep"] == 0.0
    assert ex.last_job["lost_shards"] == 1.0
    assert ex.stats["lost_shards"] == 1


@pytest.mark.parametrize("mode", list(MODES))
def test_executor_sweep_jobs_count(mode):
    with _executor(mode) as ex:
        for n in (3, 10, 0):
            ex.map_shards(_FakeCorpus(10), range(n), lambda s: s.shard_id)
        assert ex.stats["jobs"] == 3
        if mode == "sweep":
            assert ex.stats["sweep_jobs"] == 3
            assert ex._pool is None          # tiny tasks never left
        else:
            assert ex.stats["sweep_jobs"] == 0
            assert ex._pool is not None


def test_executor_sweep_hands_long_tasks_to_pool_and_speculates():
    """A job whose first task passes ``gil_floor_s`` leaves the sweep
    after that task, and a 1.5 s straggler among the rest is still
    speculated on the pool, which inherits the sweep's task times."""
    seen, threads = {}, {}
    lock = threading.Lock()

    def work(shard):
        with lock:
            n = seen.get(shard.shard_id, 0)
            seen[shard.shard_id] = n + 1
            threads[shard.shard_id] = threading.current_thread()
        time.sleep(1.5 if shard.shard_id == 9 and n == 0 else 0.01)
        return shard.shard_id

    with ShardTaskExecutor(workers=4, min_completed_for_speculation=4) as ex:
        out = ex.map_shards(_FakeCorpus(12), range(12), work)
        assert out == {i: i for i in range(12)}
        assert ex.last_job["sweep"] == 0.0
        assert ex.stats["sweep_jobs"] == 0
        assert ex._pool is not None
        assert ex.stats["speculative"] >= 1
        assert seen[9] >= 2
        # the first ran once, on the calling thread; the rest on the pool
        assert seen[0] == 1
        assert threads[0] is threading.current_thread()
        assert all(threads[i] is not threads[0] for i in range(1, 12))


def test_executor_sweep_hands_off_long_tasks_after_the_first():
    """Four 50 ms tasks that release the interpreter lock run on the
    pool after the first, not one after another: about 100 ms, where
    the calling thread alone would take 200 ms."""
    def work(shard):
        time.sleep(0.05)
        return shard.shard_id

    with ShardTaskExecutor(workers=4) as ex:
        t0 = time.perf_counter()
        out = ex.map_shards(_FakeCorpus(4), range(4), work)
        dt = time.perf_counter() - t0
        assert out == {i: i for i in range(4)}
        assert ex.last_job["sweep"] == 0.0
        assert ex.stats["sweep_jobs"] == 0
    assert dt < 0.15


def test_executor_sweep_hands_off_after_one_straggler():
    """One task past the straggler threshold (3 x 50 ms) hands the
    rest of the job to the pool even while the median is tiny."""
    def work(shard):
        if shard.shard_id == 1:
            time.sleep(0.25)
        return shard.shard_id

    with ShardTaskExecutor(workers=2) as ex:
        out = ex.map_shards(_FakeCorpus(8), range(8), work)
        assert out == {i: i for i in range(8)}
        assert ex.last_job["sweep"] == 0.0
        assert ex._pool is not None
        # a later job of tiny tasks sweeps again
        ex.map_shards(_FakeCorpus(8), range(8), lambda s: s.shard_id)
        assert ex.last_job["sweep"] == 1.0
        assert ex.stats["sweep_jobs"] == 1


# ----------------------------------------------------------------------
# checkpointing
# ----------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    import jax.numpy as jnp
    from repro.checkpoint import (
        CheckpointManager, restore_checkpoint, save_checkpoint, latest_step)
    tree = {"a": jnp.arange(10, dtype=jnp.float32),
            "b": {"c": jnp.ones((3, 4))}}
    save_checkpoint(str(tmp_path), 5, tree)
    assert latest_step(str(tmp_path)) == 5
    restored = restore_checkpoint(str(tmp_path), 5, tree)
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(restored["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_checkpoint_manager_async_and_gc(tmp_path):
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager, latest_step
    m = CheckpointManager(str(tmp_path), keep=2, async_write=True)
    tree = {"w": jnp.zeros((64,))}
    for step in (1, 2, 3, 4):
        m.save(step, tree)
    m.wait()
    assert latest_step(str(tmp_path)) == 4
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(kept) == 2


def test_checkpoint_chunked_large_leaf(tmp_path):
    import jax.numpy as jnp
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    big = jnp.arange(2 << 20, dtype=jnp.float32).reshape(1 << 11, -1)
    save_checkpoint(str(tmp_path), 1, {"big": big}, chunk_elems=1 << 18)
    r = restore_checkpoint(str(tmp_path), 1, {"big": big})
    np.testing.assert_array_equal(np.asarray(r["big"]), np.asarray(big))
    files = os.listdir(os.path.join(tmp_path, "step_1"))
    assert sum(1 for f in files if "chunk" in f) > 1


def test_checkpoint_shape_mismatch_raises(tmp_path):
    import jax.numpy as jnp
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    save_checkpoint(str(tmp_path), 1, {"a": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), 1, {"a": jnp.zeros((5,))})


# ----------------------------------------------------------------------
# optimizer / compression
# ----------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000), st.integers(1, 2000))
def test_q8_roundtrip_error_bounded(seed, n):
    from repro.optimizer.quantized import q8_dequantize, q8_quantize
    rng = np.random.default_rng(seed)
    x = rng.normal(size=n).astype(np.float32) * rng.uniform(0.01, 100)
    q = q8_quantize(x)
    back = np.asarray(q8_dequantize(q, x.shape))
    # per-block error <= absmax/254 (half a code)
    err = np.abs(back - x)
    assert err.max() <= np.abs(x).max() / 127.0 + 1e-6


def test_adamw_converges_quadratic():
    import jax
    import jax.numpy as jnp
    from repro.optimizer.adamw import AdamWConfig, adamw_init, adamw_update
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)
    params = {"x": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(params, cfg)
    for _ in range(200):
        grads = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
        params, opt, _ = adamw_update(params, grads, opt, cfg)
    assert np.abs(np.asarray(params["x"])).max() < 0.05


@pytest.mark.parametrize("state_dtype", ["bfloat16", "q8"])
def test_adamw_compressed_states(state_dtype):
    import jax
    import jax.numpy as jnp
    from repro.optimizer.adamw import AdamWConfig, adamw_init, adamw_update
    cfg = AdamWConfig(lr=0.05, weight_decay=0.0, state_dtype=state_dtype)
    params = {"w": jnp.ones((300,)) * 4.0}
    opt = adamw_init(params, cfg)
    for _ in range(150):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt, _ = adamw_update(params, grads, opt, cfg)
    assert np.abs(np.asarray(params["w"])).max() < 0.3


def test_compressed_psum_error_feedback():
    """Quantize-roundtrip residual is carried, so the *sum over steps*
    of compressed gradients tracks the true sum (error feedback)."""
    from repro.distributed.compression import quantize_roundtrip
    rng = np.random.default_rng(0)
    total_true = np.zeros(512, np.float32)
    total_sent = np.zeros(512, np.float32)
    err = np.zeros(512, np.float32)
    import jax.numpy as jnp
    for _ in range(30):
        g = rng.normal(size=512).astype(np.float32)
        total_true += g
        approx, new_err = quantize_roundtrip(jnp.asarray(g + err))
        total_sent += np.asarray(approx)
        err = np.asarray(new_err)
    drift = np.abs(total_sent + err - total_true).max()
    assert drift < 1e-3


# ----------------------------------------------------------------------
# data pipeline
# ----------------------------------------------------------------------
def test_lm_pipeline_batches(small_corpus):
    from repro.data.pipeline import LMBatchPipeline
    p = LMBatchPipeline(small_corpus, batch_size=4, seq_len=64)
    batches = list(p.iter_epoch(0))
    assert len(batches) > 3
    for b in batches:
        assert b["tokens"].shape == (4, 64)
        assert b["labels"].shape == (4, 64)
        # labels are next-token shifted wherever mask is on
        m = b["mask"][:, :-1] * b["mask"][:, 1:]
        np.testing.assert_array_equal(
            (b["labels"][:, :-1] * m).astype(np.int64),
            (b["tokens"][:, 1:] * m).astype(np.int64))


def test_prefetch_iterator():
    from repro.data.pipeline import PrefetchIterator
    it = PrefetchIterator(iter(range(100)), depth=4)
    assert list(it) == list(range(100))


def test_prefetch_propagates_errors():
    from repro.data.pipeline import PrefetchIterator

    def gen():
        yield 1
        raise ValueError("boom")

    it = PrefetchIterator(gen())
    assert next(it) == 1
    with pytest.raises(ValueError):
        list(it)


def test_similarity_sampler():
    from repro.data.pipeline import SimilaritySampler
    p = np.asarray([0.7, 0.1, 0.1, 0.1])
    s = SimilaritySampler(p, seed=0)
    draws = s.draw_epoch_order(4000)
    frac = (draws == 0).mean()
    assert 0.6 < frac < 0.8
