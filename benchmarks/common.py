"""Shared benchmark fixtures: corpora, models, indices — disk-cached so
``python -m benchmarks.run`` is resumable and re-runs are fast."""
from __future__ import annotations

import os
import pickle
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

CACHE = os.environ.get("BENCH_CACHE", "results/bench_cache")


def _device_tag() -> str:
    """Platform and device kind, part of every cache key: a setup
    pickled on one backend (a CPU run's trained model and index) must
    never be loaded by a run on another."""
    import jax
    dev = jax.devices()[0]
    return f"{dev.platform}_{dev.device_kind}".replace(" ", "-")


def cached(name, builder):
    os.makedirs(CACHE, exist_ok=True)
    path = os.path.join(CACHE, f"{name}_{_device_tag()}.pkl")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f)
    obj = builder()
    with open(path, "wb") as f:
        pickle.dump(obj, f)
    return obj


def text_setup(tag="wiki", n_docs=3200, vocab=4096, topics=16, seed=0,
               dim=64, steps=2000, beta=8.0, bits=256, kmeans=True):
    """Corpus + trained PV-DBOW + (optionally) k-means allocation +
    index.  The 'wiki'/'ccnews' tags mirror the paper's two text data
    sets (different seeds -> different topic structure)."""
    def build():
        from repro.core.allocation import allocate_corpus
        from repro.core.index import build_index
        from repro.core.lsh import LSHConfig
        from repro.core.pv_dbow import PVDBOWConfig, train_pv_dbow
        from repro.data.corpus import SyntheticCorpusConfig, generate_text_corpus

        ccfg = SyntheticCorpusConfig(n_docs=n_docs, vocab_size=vocab,
                                     n_topics=topics, seed=seed)
        docs, _ = generate_text_corpus(ccfg)
        from repro.data.store import ShardedCorpus
        corpus = ShardedCorpus.from_documents(docs, vocab, shard_tokens=4096)
        pcfg = PVDBOWConfig(dim=dim, steps=steps, batch_pairs=4096,
                            lr=0.01, temperature=beta, seed=seed)
        t0 = time.time()
        model = train_pv_dbow(corpus, pcfg)
        train_s = time.time() - t0
        if kmeans:
            pre = build_index(corpus, model, LSHConfig(bits=bits),
                              use_lsh=False, temperature=beta)
            corpus = allocate_corpus(corpus, pre.doc_vecs)
        index = build_index(corpus, model, LSHConfig(bits=bits),
                            temperature=beta)
        return dict(corpus=corpus, model=model, index=index,
                    train_s=train_s, pv_cfg=pcfg)
    return cached(f"text_{tag}_{n_docs}_{vocab}_{dim}_{steps}_{bits}"
                  f"_{int(kmeans)}_{seed}", build)


def review_setup(n_users=400, n_items=200, vocab=4096, topics=12, seed=1,
                 dim=48, steps=1500, beta=8.0, bits=256):
    """Amazon-reviews analogue for the recommendation workload."""
    def build():
        from repro.core.allocation import allocate_corpus
        from repro.core.index import build_index
        from repro.core.lsh import LSHConfig
        from repro.core.pv_dbow import PVDBOWConfig, train_pv_dbow
        from repro.data.corpus import ReviewCorpusConfig, generate_review_corpus
        from repro.data.store import ShardedCorpus

        data = generate_review_corpus(ReviewCorpusConfig(
            n_users=n_users, n_items=n_items, vocab_size=vocab,
            n_topics=topics, seed=seed))
        corpus = ShardedCorpus.from_documents(data.user_docs, vocab,
                                              shard_tokens=2048)
        pcfg = PVDBOWConfig(dim=dim, steps=steps, batch_pairs=4096,
                            lr=0.01, temperature=beta, seed=seed)
        model = train_pv_dbow(corpus, pcfg)
        pre = build_index(corpus, model, LSHConfig(bits=bits),
                          use_lsh=False, temperature=beta)
        corpus_km = allocate_corpus(corpus, pre.doc_vecs)
        index = build_index(corpus_km, model, LSHConfig(bits=bits),
                            temperature=beta)
        return dict(data=data, corpus=corpus_km, model=model, index=index,
                    pv_cfg=pcfg)
    return cached(f"review_{n_users}_{n_items}_{dim}_{steps}_{bits}_{seed}",
                  build)


def pick_query_words(corpus, n, rng, lo=50, hi=1200):
    counts = np.bincount(
        np.concatenate([s.tokens for s in corpus.shards]),
        minlength=corpus.vocab_size)
    cand = np.nonzero((counts > lo) & (counts < hi))[0]
    return rng.choice(cand, min(n, len(cand)), replace=False).astype(int)


def pick_query_phrases(corpus, n, rng, mean_len=2.0, std_len=1.0,
                       min_count=20):
    """Paper Sec. VII-A: random phrases, length ~ N(2, 1) clipped >= 1,
    drawn from actual corpus positions so they exist.

    ``min_count`` filters out near-singleton phrases: at 62 GB corpus
    scale the paper's random 2-word phrases occur thousands of times; at
    our ~13 MB synthetic scale they are often singletons, which turns
    relative error into a coin flip for EVERY sampling method.  The
    filter keeps the estimator regime comparable to the paper's."""
    phrases = []
    shards = corpus.shards
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
            continue  # don't cross doc boundary
        ph = s.tokens[start:start + k].tolist()
        if min_count and corpus.count_phrase(ph) < min_count:
            continue
        phrases.append(ph)
    return phrases


def csv_row(name, us_per_call, derived):
    print(f"{name},{us_per_call:.1f},{derived}")
