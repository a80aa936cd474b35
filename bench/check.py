"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
of the answered queries, drawn from the seed, is answered again by
``bench/reference.py``.  The numbers compared:

* ``sig_wrong`` -- signature bits of the program's index that disagree
  with the reference's rebuild of it (signing, centring, shard means).
* ``plan_gap`` -- the largest relative gap between a sampling
  probability the planner gave a query and the reference's, over the
  sampled queries and every shard.
* ``draw_z`` -- how far the draws of the sampled queries lie from the
  reference's sampling law of its own probabilities: the sum over every
  draw of log(n p), less its mean under the law, over its standard
  deviation (|z|; a sound sampler reads about 1).
* ``count_wrong``, ``bool_wrong``, ``ranked_wrong`` -- sampled queries
  of each kind whose answer differs from the reference's over the same
  draws (the Hansen-Hurwitz estimate, the document set, the top-k ids
  and scores), or whose draws break the sampler's rule (rate times
  shards, with replacement for counts, distinct for retrieval).
* ``unanswered`` -- queries offered in the window that never resolved
  within a minute of its close, or raised.

Each has its limit in the configuration file.  With ``control`` the
reference in bfloat16 takes the planner's place: its probabilities go
where the program's would, through the same comparison.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from bench.reference import Reference

NUMBERS = ("sig_wrong", "plan_gap", "draw_z", "count_wrong", "bool_wrong",
           "ranked_wrong", "unanswered")


def pick(queries: List[tuple], answered: List[int], per_kind: Dict[str, int],
         rng: np.random.Generator) -> List[int]:
    """Up to ``per_kind[kind]`` answered queries of each kind."""
    out = []
    for kind, n in per_kind.items():
        idx = [i for i in answered if queries[i][0] == kind]
        if idx:
            out += rng.choice(idx, min(n, len(idx)), replace=False).tolist()
    return sorted(out)


def _draw_ok(kind: str, ids: np.ndarray, rate: float, n_shards: int) -> bool:
    n = max(1, int(math.ceil(rate * n_shards)))
    if kind == "count":
        return len(ids) == n and bool(((ids >= 0) & (ids < n_shards)).all())
    return (len(ids) == min(n, n_shards) and len(np.unique(ids)) == len(ids)
            and bool(((ids >= 0) & (ids < n_shards)).all()))


def compare(ref: Reference, queries: List[tuple], results: List,
            rate: float, rng: np.random.Generator,
            control: bool = False) -> Dict[str, float]:
    """The numbers above (all but ``unanswered``) for ``results[i]``, the
    program's answer to ``queries[i]``."""
    out = dict(sig_wrong=ref.index()["sig_wrong"], plan_gap=0.0,
               draw_z=0.0, count_wrong=0, bool_wrong=0, ranked_wrong=0)
    if not queries:
        return out
    want = ref.plan_scores(queries)
    got = (ref.plan_scores(queries, dtype="bfloat16") if control
           else np.stack([r.sample.probabilities for r in results]))
    out["plan_gap"] = float(np.max(np.abs(got / want - 1.0)))
    dev = var = 0.0
    for q, r, p in zip(queries, results, want):
        ids = np.asarray(r.sample.shard_ids, np.int64)
        ok = _draw_ok(q[0], ids, rate, ref.n_shards)
        if ok:
            s, mu, v = ref.draw_stat(p, ids, q[0] != "count", rng)
            dev, var = dev + s - mu, var + v
        if ok and q[0] == "count":
            ok = r.estimate.value == ref.count(q[1], ids,
                                               r.sample.probabilities)
        elif ok and q[0] == "bool":
            ok = np.array_equal(np.sort(r.doc_ids), ref.boolean(q[1], ids))
        elif ok:
            want_ids, want_sc = ref.ranked(q[1], q[2], ids)
            ok = (np.array_equal(r.doc_ids, want_ids)
                  and np.array_equal(r.scores, want_sc))
        if not ok:
            key = {"count": "count_wrong", "bool": "bool_wrong",
                   "ranked": "ranked_wrong"}[q[0]]
            out[key] += 1
    out["draw_z"] = abs(dev) / math.sqrt(var) if var > 0 else 0.0
    return out
