"""Offering load to the serving stack, and the host spans around the
calls into each layer.

Open loop: one thread submits each query at its due time and the
query's sojourn runs from that due time to the moment its future
resolves, so a stall of the generator or of the window counts against
every query it delays.  Should the generator stall for ``STALL_S``, the
Python stack of every thread goes to standard error, to show where the
process stood.  Closed loop: each client submits its next
query from the callback that resolves its previous one.

``Layers`` wraps the calls into the engine, the planner and the
executor from outside (the program is not edited), recording a span
per call, and a ``jax.profiler.TraceAnnotation`` around each (they
are wrapped only while a trace is on), so that the device trace's idle gaps can be put down to
what the host was doing.
"""
from __future__ import annotations

import faulthandler
import functools
import itertools
import sys
import threading
import time
from concurrent.futures import wait
from typing import Any, Dict, List, Optional

import numpy as np


class Compiles:
    """Counts backend compiles and persistent-cache hits (a copy of
    ``chip_smoke.Compiles``)."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Layers:
    """Spans of the calls into each layer of ``stack``.  ``batches`` has
    one record per ``QueryBatch.execute``: its entry time, the ids of
    its queries, and the host seconds spent planning and scanning.
    ``calls`` has one (rows, time) record per call of the segment-sum
    planner."""

    def __init__(self, stack):
        self.batches: List[Dict[str, Any]] = []
        self.calls: List[tuple] = []
        self._cur: Optional[Dict[str, Any]] = None
        self._undo: List[tuple] = []
        index = stack.engine.index
        self._wrap(stack.engine, "execute", self._execute)
        self._wrap(index, "shard_similarities_batch", functools.partial(
            self._layer, "plan_s", "bench.plan", True))
        self._wrap(index, "word_shard_similarities_batch", functools.partial(
            self._layer, "plan_s", "bench.plan", False))
        self._wrap(stack.executor, "map_shard_batch", functools.partial(
            self._layer, "scan_s", "bench.scan", False))

    def _wrap(self, obj, name: str, fn) -> None:
        orig = getattr(obj, name)
        setattr(obj, name, lambda *a, **kw: fn(orig, *a, **kw))
        self._undo.append((obj, name))

    def close(self) -> None:
        for obj, name in self._undo:
            delattr(obj, name)
        self._undo = []

    @staticmethod
    def _span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _execute(self, orig, queries, *a, **kw):
        rec = dict(t=time.perf_counter(), ids=[id(q) for q in queries],
                   plan_s=0.0, scan_s=0.0)
        self._cur = rec
        try:
            with self._span("bench.execute"):
                return orig(queries, *a, **kw)
        finally:
            self._cur = None
            self.batches.append(rec)

    def _layer(self, key: str, span: str, segsum: bool, orig, *a, **kw):
        t0 = time.perf_counter()
        if segsum:
            self.calls.append((len(a[0]), t0))
        try:
            with self._span(span):
                return orig(*a, **kw)
        finally:
            if self._cur is not None:
                self._cur[key] += time.perf_counter() - t0


class Offered:
    """What was offered and when: per query its due time, submit time,
    completion time and future (times on ``time.perf_counter``)."""

    def __init__(self, n: int, on_done=None):
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.futs: List[Any] = [None] * n
        self.queries: List[Any] = [None] * n
        self.used = 0
        self._on_done = on_done

    def _finish(self, i: int, fut) -> None:
        self.done[i] = time.perf_counter()
        if self._on_done is not None:
            self._on_done(i)

    def submit(self, window, i: int, q, due: float) -> None:
        self.queries[i] = q
        self.due[i] = due
        self.sent[i] = time.perf_counter()
        fut = window.submit(q)
        self.futs[i] = fut
        fut.add_done_callback(lambda f, i=i: self._finish(i, f))

    def settle(self, deadline: float) -> None:
        """Wait for every submitted future, until ``deadline``."""
        futs = [f for f in self.futs[:self.used] if f is not None]
        wait(futs, timeout=max(0.0, deadline - time.perf_counter()))


STALL_S = 1.0


def open_loop(window, queries: List[Any], due_s: np.ndarray,
              t0: float) -> Offered:
    """Submit ``queries[i]`` at ``t0 + due_s[i]``."""
    off = Offered(len(queries))
    try:
        for i, (q, d) in enumerate(zip(queries, due_s)):
            due = t0 + float(d)
            pause = due - time.perf_counter()
            faulthandler.dump_traceback_later(STALL_S + max(pause, 0.0),
                                              file=sys.stderr)
            if pause > 0:
                time.sleep(pause)
            off.submit(window, i, q, due)
            off.used = i + 1
    finally:
        faulthandler.cancel_dump_traceback_later()
    return off


def closed_loop(window, pool: List[Any], clients: int, t0: float,
                seconds: float) -> Offered:
    """``clients`` clients, each sending its next query as soon as the
    previous one is answered, until ``t0 + seconds``.  Queries are taken
    from ``pool`` in order (it wraps only if the run outpaces it)."""
    cap = max(len(pool), 1) * 4
    end = t0 + seconds
    off = Offered(cap, on_done=lambda i: off.done[i] < end and issue())
    counter = itertools.count()
    lock = threading.Lock()

    def issue() -> None:
        with lock:
            i = next(counter)
            if i >= cap:
                return
            off.used = max(off.used, i + 1)
        now = time.perf_counter()
        off.submit(window, i, pool[i % len(pool)], now)

    pause = t0 - time.perf_counter()
    if pause > 0:
        time.sleep(pause)
    for _ in range(clients):
        issue()
    time.sleep(max(0.0, end - time.perf_counter()))
    return off
