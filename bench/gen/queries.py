"""Query mixes and arrivals, drawn from a traffic file and a seed.

``pick_query_phrases`` and ``pick_query_words`` are copies of the
program's benchmark helpers (paper Sec. VII-A: phrases of length
N(2, 1), clipped at 1, drawn from corpus positions so they exist), with
the corpus passed as plain shard views and an exact counter; the rest
turns a traffic file's parameters into a list of distinct queries, as
plain tuples the harness hands to the program:

  ("count", (w, ...))            occurrences of a phrase
  ("bool", ("and", a, b))        documents holding a and b
  ("bool", ("or-and", a, b, c))  documents holding (a or b) and c
  ("ranked", (w, ...), k)        BM25 top-k over the words
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence

import numpy as np


def pick_query_words(counts: np.ndarray, n: int, rng: np.random.Generator,
                     lo: int = 50, hi: int = 1200) -> np.ndarray:
    """``n`` distinct words whose corpus count lies in (lo, hi);
    ``counts`` is the corpus's per-word token count."""
    cand = np.nonzero((counts > lo) & (counts < hi))[0]
    return rng.choice(cand, min(n, len(cand)), replace=False).astype(int)


def pick_query_phrases(shards: Sequence[Any], n: int,
                       rng: np.random.Generator,
                       count: Callable[[List[int]], int],
                       mean_len: float = 2.0, std_len: float = 1.0,
                       min_count: int = 20) -> List[List[int]]:
    """``n`` phrases read at random positions of random shards, never
    across a document boundary, each occurring at least ``min_count``
    times in the corpus (``count`` gives the exact corpus count).  A
    shard view has ``tokens``, ``offsets`` and ``n_tokens``."""
    phrases = []
    attempts = 0
    while len(phrases) < n and attempts < n * 30:
        attempts += 1
        k = max(1, int(round(rng.normal(mean_len, std_len))))
        s = shards[rng.integers(len(shards))]
        if s.n_tokens < k + 1:
            continue
        start = rng.integers(0, s.n_tokens - k)
        doc = np.searchsorted(s.offsets, start, side="right") - 1
        if start + k > s.offsets[doc + 1]:
            continue
        ph = s.tokens[start:start + k].tolist()
        if min_count and count(ph) < min_count:
            continue
        phrases.append(ph)
    return phrases


def _draw(kind: str, t: Dict[str, Any], rng, counts, shards, count_fn):
    lo, hi = t.get("word_count_range", (50, 1200))
    if kind == "count":
        ph = t["phrase"]
        if ph.get("single_word"):
            return ("count", (int(pick_query_words(counts, 1, rng, lo, hi)[0]),))
        got = pick_query_phrases(shards, 1, rng, count_fn, ph["mean_len"],
                                 ph["std_len"], ph["min_count"])
        return ("count", tuple(got[0])) if got else None
    if kind == "bool":
        forms = t["bool_forms"]
        form = forms[int(rng.integers(len(forms)))]
        w = [int(x) for x in pick_query_words(
            counts, 2 if form == "and" else 3, rng, lo, hi)]
        return ("bool", (form, *w))
    a, b = t["ranked_words"]
    k = int(rng.integers(a, b + 1))
    w = tuple(int(x) for x in pick_query_words(counts, k, rng, lo, hi))
    return ("ranked", w, int(t["ranked_k"]))


def draw_queries(t: Dict[str, Any], n: int, rng: np.random.Generator,
                 counts: np.ndarray, shards: Sequence[Any],
                 count_fn: Callable[[List[int]], int],
                 exclude=frozenset()) -> List[tuple]:
    """``n`` distinct queries in the traffic's mix, none in ``exclude``:
    each kind gets its share of ``n`` exactly (so every seed offers the
    same amount of each kind), and the order is shuffled."""
    mix = t["mix"]
    kinds = list(mix)
    sizes = [int(round(mix[k] * n)) for k in kinds]
    sizes[-1] = n - sum(sizes[:-1])
    seen = set(exclude)
    out: List[tuple] = []
    for kind, size in zip(kinds, sizes):
        got, tries = 0, 0
        while got < size:
            tries += 1
            if tries > 50 * size + 100:
                raise RuntimeError(f"cannot draw {size} distinct {kind} "
                                   f"queries from this corpus")
            q = _draw(kind, t, rng, counts, shards, count_fn)
            if q is None or q in seen:
                continue
            seen.add(q)
            out.append(q)
            got += 1
    order = rng.permutation(len(out))
    return [out[i] for i in order]


def open_arrivals(rate_qps: float, seconds: float, rng: np.random.Generator,
                  start: int = 0) -> np.ndarray:
    """Due times in [0, seconds) of a Poisson stream at ``rate_qps``,
    conditioned on its mean count: the gaps between round(rate *
    seconds) sorted uniform times drawn from ``rng``, taken in turn from
    gap ``start`` and round to the first, so every start offers the same
    cycle of gaps, bursts included."""
    n = int(round(rate_qps * seconds))
    gaps = np.diff(np.sort(rng.uniform(0.0, seconds, n)), prepend=0.0)
    return np.cumsum(np.roll(gaps, -start))
