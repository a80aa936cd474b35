import os
import sys

# Tests must see the real device count (1 CPU), never the dry-run's 512.
os.environ.pop("XLA_FLAGS", None)
# The suite runs on the CPU even where a TPU is attached: a test run
# must never take the chip.  Kernel compiles for the TPU go through a
# described topology (tests/test_tpu_compile.py), not a device.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="session")
def small_corpus():
    from repro.data.corpus import SyntheticCorpusConfig, generate_text_corpus
    from repro.data.store import ShardedCorpus
    cfg = SyntheticCorpusConfig(n_docs=600, vocab_size=2048, n_topics=8,
                                seed=11)
    docs, topics = generate_text_corpus(cfg)
    corpus = ShardedCorpus.from_documents(docs, cfg.vocab_size,
                                          shard_tokens=4096)
    return corpus


@pytest.fixture(scope="session")
def pv_model(small_corpus):
    from repro.core.pv_dbow import PVDBOWConfig, train_pv_dbow
    cfg = PVDBOWConfig(dim=24, steps=400, batch_pairs=2048, lr=0.01,
                       temperature=8.0, seed=5)
    return train_pv_dbow(small_corpus, cfg), cfg


@pytest.fixture(scope="session")
def built_index(small_corpus, pv_model):
    from repro.core.index import build_index
    from repro.core.lsh import LSHConfig
    model, pcfg = pv_model
    return build_index(small_corpus, model, LSHConfig(bits=128),
                       temperature=pcfg.temperature)
