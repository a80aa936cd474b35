"""PV-DBOW training + index behaviour."""
import os

import numpy as np
import pytest

from repro.core.index import ApproxIndex, build_index
from repro.core.lsh import LSHConfig
from repro.core.pv_dbow import (
    PVDBOWConfig,
    corpus_pairs,
    infer_doc_vector,
    train_pv_dbow,
)


def test_training_reduces_loss(small_corpus):
    losses = []
    cfg = PVDBOWConfig(dim=16, steps=250, batch_pairs=2048, lr=0.01,
                       temperature=8.0)
    train_pv_dbow(small_corpus, cfg,
                  callback=lambda s, loss: losses.append(loss))
    assert losses[-1] < losses[0] * 0.85


def test_vectors_unit_norm(pv_model):
    model, _ = pv_model
    for t in (model.word_vecs, model.doc_vecs):
        norms = np.linalg.norm(np.asarray(t), axis=1)
        np.testing.assert_allclose(norms, 1.0, atol=1e-4)


def test_corpus_pairs_subsampling(small_corpus):
    full = corpus_pairs(small_corpus, subsample_t=0.0)
    sub = corpus_pairs(small_corpus, subsample_t=1e-3)
    assert sub.word_of_token.shape[0] < full.word_of_token.shape[0]
    assert sub.noise_cdf[-1] == pytest.approx(1.0, abs=1e-5)
    assert (np.diff(sub.noise_cdf) >= 0).all()


def test_infer_unseen_document(small_corpus, pv_model):
    """Inferred vector for an existing doc's tokens should land near
    that doc's trained vector (paper Sec. V)."""
    model, cfg = pv_model
    doc = small_corpus.shards[0].document(0)
    vec = np.asarray(infer_doc_vector(model, doc.tokens, cfg, steps=100))
    dv = np.asarray(model.doc_vecs)
    sims = dv @ vec
    rank = (sims > sims[doc.doc_id]).sum()
    assert rank < len(dv) * 0.25   # top quartile


def test_index_roundtrip(tmp_path, built_index):
    p = os.path.join(tmp_path, "idx.npz")
    built_index.save(p)
    loaded = ApproxIndex.load(p)
    np.testing.assert_array_equal(loaded.shard_sig, built_index.shard_sig)
    assert loaded.bits == built_index.bits
    assert loaded.temperature == built_index.temperature
    q = built_index.shard_probabilities([3, 5])
    q2 = loaded.shard_probabilities([3, 5])
    np.testing.assert_allclose(q, q2, rtol=1e-6)


def test_shard_probabilities_valid(built_index):
    p = built_index.shard_probabilities([1, 2, 3])
    assert p.sum() == pytest.approx(1.0)
    assert (p > 0).all()


def test_index_compression(built_index, small_corpus):
    """LSH index must be far smaller than raw fp32 vectors (paper
    Table II: ~64x)."""
    raw = (built_index.word_vecs.nbytes + built_index.doc_vecs.nbytes +
           built_index.shard_vecs.nbytes)
    packed = (built_index.word_sig.nbytes + built_index.doc_sig.nbytes +
              built_index.shard_sig.nbytes)
    assert packed * 4 < raw


def test_doc_granularity_scoring(small_corpus, pv_model):
    model, pcfg = pv_model
    idx = build_index(small_corpus, model, LSHConfig(bits=128),
                      temperature=pcfg.temperature, granularity="doc")
    p = idx.shard_probabilities([7])
    assert p.shape[0] == small_corpus.n_shards
    assert p.sum() == pytest.approx(1.0)
