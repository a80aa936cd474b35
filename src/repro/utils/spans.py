"""Named spans on the serving path, on the profiler's clock.

``span(name, **meta)`` opens and yields a
``jax.profiler.TraceAnnotation`` (its ``set_metadata`` adds to ``meta``
before the span closes).  While a profiler session runs, the span lands
on the host plane of the device trace, on the trace's clock, nested
under the spans open on its thread; otherwise it costs about a
microsecond.  Every name starts with ``emapprox.``.

``batch(seq, stages)`` marks one engine batch in flight on this thread:
it opens the batch's root span and collects, for each stage key, the
*self* seconds (``time.perf_counter``) of the spans named
``emapprox.<key>`` (dots in the name become ``_`` in the key) opened on
this thread while the batch runs.  A span whose key is not a stage is
only annotated; its time stays with the stage around it.  Every span of
the batch carries ``batch=<seq>``.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterable, Iterator, List

from jax.profiler import TraceAnnotation

PREFIX = "emapprox."

_local = threading.local()


class Batch:
    """Stage totals of one batch in flight."""

    def __init__(self, seq: int, stages: Iterable[str]):
        self.seq = int(seq)
        self.stages: Dict[str, float] = dict.fromkeys(stages, 0.0)
        self._open: List[List[float]] = []    # child seconds per open stage


def _stage_key(name: str) -> str:
    """``emapprox.plan.wait`` -> ``plan_wait``."""
    return name[len(PREFIX):].replace(".", "_")


@contextlib.contextmanager
def batch(seq: int, stages: Iterable[str],
          name: str = PREFIX + "execute", **meta) -> Iterator[Batch]:
    rec = Batch(seq, stages)
    outer = getattr(_local, "batch", None)
    _local.batch = rec
    try:
        with TraceAnnotation(name, batch=rec.seq, **meta):
            yield rec
    finally:
        _local.batch = outer


@contextlib.contextmanager
def span(name: str, **meta) -> Iterator[TraceAnnotation]:
    rec = getattr(_local, "batch", None)
    if rec is None:
        with TraceAnnotation(name, **meta) as ann:
            yield ann
        return
    key = _stage_key(name)
    with TraceAnnotation(name, batch=rec.seq, **meta) as ann:
        if key not in rec.stages:
            yield ann
            return
        child = [0.0]
        rec._open.append(child)
        t0 = time.perf_counter()
        try:
            yield ann
        finally:
            dur = time.perf_counter() - t0
            rec._open.pop()
            rec.stages[key] += dur - child[0]
            if rec._open:
                rec._open[-1][0] += dur
