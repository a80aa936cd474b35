"""Synthetic text corpora for the benchmark's deployments.

A copy of the program's topic-mixture generator (the Wikipedia/CCNews
analogue: Zipfian within-topic word laws, topic-exclusive vocabulary
heads, topic-clustered arrival order).  It is kept here so that no
change to the program can change the data a cell is measured on;
``bench/tests/test_gen.py`` checks that it draws exactly what the
program's generator draws at the same seed.

The copy returns flat arrays instead of one object per document, and
assigns word topics in blocks of tokens: the same random draws in the
same order, without the [tokens, topics] temporary that the original
builds at once (6 GB at 32M tokens).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import numpy as np

_BLOCK = 1 << 20


@dataclasses.dataclass(frozen=True)
class Corpus:
    """Documents in arrival order: document ``i`` is
    ``tokens[offsets[i]:offsets[i + 1]]``."""
    tokens: np.ndarray        # int32 [n_tokens]
    offsets: np.ndarray       # int64 [n_docs + 1]
    vocab_size: int

    @property
    def n_docs(self) -> int:
        return int(self.offsets.shape[0] - 1)


def _topic_word_dists(p: Dict[str, Any], rng: np.random.Generator):
    vocab, n_topics, z = p["vocab_size"], p["n_topics"], p["zipf_exponent"]
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    shared = ranks ** (-z)
    shared /= shared.sum()
    shared = shared[rng.permutation(vocab)]
    block = vocab // (n_topics + 1)
    dists = np.empty((n_topics, vocab), np.float64)
    for t in range(n_topics):
        own = np.zeros(vocab, np.float64)
        lo, hi = t * block, (t + 1) * block
        local_ranks = np.arange(1, hi - lo + 1, dtype=np.float64)
        own_p = local_ranks ** (-z)
        own[lo + rng.permutation(hi - lo)] = own_p / own_p.sum()
        dists[t] = 0.3 * shared + 0.7 * own
    return dists


def generate(p: Dict[str, Any], seed: int) -> Corpus:
    """The corpus that ``p`` (a configuration's corpus keys) describes,
    drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    n_docs, n_topics = p["n_docs"], p["n_topics"]
    topic_dists = _topic_word_dists(p, rng)
    doc_topics = rng.dirichlet(np.full(n_topics, p["doc_topic_alpha"]),
                               size=n_docs)
    lengths = np.clip(
        rng.normal(p["mean_doc_len"], p["std_doc_len"], n_docs)
        .astype(np.int64), p["min_doc_len"], None)
    total = int(lengths.sum())
    doc_index = np.repeat(np.arange(n_docs), lengths)
    u = rng.random(total)
    cum = np.cumsum(doc_topics, axis=1)
    word_topic = np.empty(total, np.int16)
    for lo in range(0, total, _BLOCK):
        hi = min(total, lo + _BLOCK)
        word_topic[lo:hi] = (u[lo:hi, None] > cum[doc_index[lo:hi]]).sum(1)
    del u, doc_index
    tokens = np.empty(total, np.int32)
    for t in range(n_topics):
        mask = word_topic == t
        n = int(mask.sum())
        if n:
            tokens[mask] = rng.choice(p["vocab_size"], size=n,
                                      p=topic_dists[t]).astype(np.int32)
    offsets = np.zeros(n_docs + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])

    if p["topic_locality"] > 0:
        dominant = doc_topics.argmax(axis=1).astype(np.float64)
        noise = rng.normal(0, (1.0 - p["topic_locality"]) * n_topics + 1e-9,
                           n_docs)
        order = np.argsort(dominant + noise, kind="stable")
    else:
        order = np.arange(n_docs)
    new_len = lengths[order]
    new_off = np.zeros(n_docs + 1, np.int64)
    np.cumsum(new_len, out=new_off[1:])
    src = np.repeat(offsets[order] - new_off[:-1], new_len)
    src += np.arange(total, dtype=np.int64)
    return Corpus(tokens[src], new_off, int(p["vocab_size"]))
