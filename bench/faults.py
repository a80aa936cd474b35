"""Faults planted in the timed path, to show that the comparison that
decides ``correct`` catches each: every one is a hook that takes the
serving stack before the window and breaks one layer underneath it.
``bench/tests/test_correct.py`` runs each at CPU size, and
``bench/control.py --fault <name>`` reads one on the chip.
"""
from __future__ import annotations

from concurrent.futures import Future

import numpy as np


def _alter(kind: str):
    """The first answer of ``kind`` in every batch altered where the
    engine returns it."""
    def hook(stack):
        engine = stack.engine
        orig = engine.execute

        def execute(queries, *a, **kw):
            out = orig(queries, *a, **kw)
            for i, q in enumerate(queries):
                if q.kind != kind:
                    continue
                r = out[i]
                if kind == "count":
                    out[i] = r._replace(estimate=r.estimate._replace(
                        value=r.estimate.value + 1.0))
                elif kind == "bool":
                    out[i] = r._replace(doc_ids=r.doc_ids[:-1]
                                        if len(r.doc_ids)
                                        else np.zeros(1, np.int64))
                else:
                    out[i] = r._replace(scores=r.scores * (1 + 1e-12))
                break
            return out
        engine.execute = execute
    return hook


def plan_off(stack) -> None:
    """The planner's score of shard 0 off by 1%."""
    index = stack.engine.index
    orig = index.shard_similarities_batch

    def scores(*a, **kw):
        s = orig(*a, **kw)
        s[:, 0] *= 1.01
        return s
    index.shard_similarities_batch = scores


def uniform_sampler(stack) -> None:
    """Draws taken uniformly, whatever the probabilities (which are
    still reported): the sampler ignores the planner.  The engine's
    samplers are put back when the stack closes."""
    from repro.core.queries import batch
    from repro.core.sampling import SampleResult

    def draw(distinct):
        def sample(p, rate, rng):
            n = len(p)
            m = max(1, int(np.ceil(rate * n)))
            ids = (np.sort(rng.choice(n, min(m, n), replace=False))
                   if distinct else rng.integers(0, n, m))
            return SampleResult(ids.astype(np.int64),
                                np.asarray(p, np.float64), rate)
        return sample
    saved = batch.pps_sample, batch.pps_sample_distinct
    batch.pps_sample, batch.pps_sample_distinct = draw(False), draw(True)
    close = stack.close

    def restore():
        batch.pps_sample, batch.pps_sample_distinct = saved
        close()
    stack.close = restore


def never_answered(stack) -> None:
    """The third query submitted never resolves."""
    window = stack.window
    orig = window.submit
    n = [0]

    def submit(q):
        n[0] += 1
        return Future() if n[0] == 3 else orig(q)
    window.submit = submit


FAULTS = {
    "count": _alter("count"),
    "bool": _alter("bool"),
    "ranked": _alter("ranked"),
    "plan": plan_off,
    "uniform": uniform_sampler,
    "unanswered": never_answered,
}
