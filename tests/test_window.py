"""Warm adaptive serving runtime: BatchWindow deadline/size/flush close
behavior under synthetic arrival traces, error delivery, and the
end-to-end window -> QueryBatch -> warm executor path."""
import threading
import time

import pytest

from repro.runtime import BatchWindow, ShardTaskExecutor


class _RecordingEngine:
    """Stands in for QueryBatch: records every executed batch."""

    def __init__(self, delay_s: float = 0.0):
        self.batches = []
        self.delay_s = delay_s
        self._lock = threading.Lock()

    def execute(self, queries, rate, rng=None):
        if self.delay_s:
            time.sleep(self.delay_s)
        with self._lock:
            self.batches.append(list(queries))
        return [("done", q, rate) for q in queries]


def test_window_closes_by_size():
    eng = _RecordingEngine()
    with BatchWindow(eng, 0.5, max_batch=4, max_delay_s=30.0) as win:
        futs = [win.submit(i) for i in range(8)]
        results = [f.result(timeout=10) for f in futs]
    assert results == [("done", i, 0.5) for i in range(8)]
    assert win.stats["closed_by_size"] == 2
    assert win.stats["closed_by_deadline"] == 0
    assert win.stats["served"] == 8
    assert [len(b) for b in eng.batches] == [4, 4]


def test_window_closes_by_deadline():
    eng = _RecordingEngine()
    win = BatchWindow(eng, 0.5, max_batch=100, max_delay_s=0.05)
    t0 = time.perf_counter()
    futs = [win.submit(i) for i in range(3)]
    results = [f.result(timeout=10) for f in futs]
    waited = time.perf_counter() - t0
    win.close()
    assert [r[1] for r in results] == [0, 1, 2]
    assert win.stats["closed_by_deadline"] == 1
    assert win.stats["closed_by_size"] == 0
    # the batch waited for the deadline, not for max_batch arrivals
    assert 0.04 <= waited < 5.0
    assert eng.batches == [[0, 1, 2]]


def test_window_synthetic_trace_mixes_close_reasons():
    """A burst (size close) followed by a trickle (deadline close)."""
    eng = _RecordingEngine()
    win = BatchWindow(eng, 1.0, max_batch=5, max_delay_s=0.05)
    futs = [win.submit(i) for i in range(5)]          # burst: exactly one
    [f.result(timeout=10) for f in futs]              # full window
    late = win.submit(99)                             # lone straggler
    assert late.result(timeout=10)[1] == 99
    win.close()
    assert win.stats["closed_by_size"] == 1
    assert win.stats["closed_by_deadline"] == 1
    assert win.stats["batches"] == 2


def test_window_flush_and_close_drain():
    eng = _RecordingEngine()
    win = BatchWindow(eng, 1.0, max_batch=100, max_delay_s=30.0)
    f1 = win.submit("a")
    win.flush()
    assert f1.result(timeout=10)[1] == "a"
    assert win.stats["closed_by_flush"] == 1
    f2 = win.submit("b")
    win.close()                        # close() must drain the open window
    assert f2.result(timeout=10)[1] == "b"
    assert win.stats["served"] == 2
    with pytest.raises(RuntimeError):
        win.submit("c")


def test_window_survives_cancelled_futures():
    """Regression: a caller cancelling a pending future must not kill
    the dispatcher (set_result on a cancelled future raises)."""
    eng = _RecordingEngine()
    win = BatchWindow(eng, 1.0, max_batch=100, max_delay_s=0.05)
    doomed = win.submit("doomed")
    assert doomed.cancel()
    ok = win.submit("ok")
    assert ok.result(timeout=10)[1] == "ok"      # dispatcher still alive
    later = win.submit("later")
    assert later.result(timeout=10)[1] == "later"
    win.close()
    assert win.stats["cancelled"] == 1
    assert win.stats["served"] == 2
    assert all("doomed" not in b for b in eng.batches)


def test_window_delivers_engine_failures():
    class Boom:
        def execute(self, queries, rate, rng=None):
            raise RuntimeError("engine exploded")

    win = BatchWindow(Boom(), 1.0, max_batch=2, max_delay_s=0.01)
    f1, f2 = win.submit(1), win.submit(2)
    for f in (f1, f2):
        with pytest.raises(RuntimeError):
            f.result(timeout=10)
    win.close()


def test_window_rejects_bad_config():
    eng = _RecordingEngine()
    with pytest.raises(ValueError):
        BatchWindow(eng, 1.0, max_batch=0)
    with pytest.raises(ValueError):
        BatchWindow(eng, 1.0, max_delay_s=-1.0)


def test_window_end_to_end_precise(small_corpus, built_index):
    """Window -> QueryBatch -> warm executor at rate 1.0: the precise
    answers must be independent of how arrivals were windowed."""
    from repro.core.queries import BatchQuery, QueryBatch, parse_boolean
    ex = ShardTaskExecutor(workers=2, gil_floor_s=0.0)   # pinned to the pool
    engine = QueryBatch(small_corpus, built_index, executor=ex)
    queries = [BatchQuery.count([5]),
               BatchQuery.boolean(parse_boolean([4, "or", 9])),
               BatchQuery.count([7, 2]),
               BatchQuery.ranked([3, 8], k=5)]
    with BatchWindow(engine, 1.0, max_batch=3, max_delay_s=0.02) as win:
        futs = [win.submit(q) for q in queries]
        results = [f.result(timeout=60) for f in futs]
    assert results[0].estimate.value == small_corpus.count_phrase([5])
    assert results[2].estimate.value == small_corpus.count_phrase([7, 2])
    assert results[0].shards_read == small_corpus.n_shards
    # warm pool was reused across windows, not rebuilt per batch
    assert ex.stats["jobs"] >= 2
    assert ex.stats["pool_rebuilds"] == 1
    ex.close()
