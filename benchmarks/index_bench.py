"""Paper Table II analogue: index sizes, PV-DBOW training throughput,
query-time index-lookup cost (the XOR-Hamming hot path) for both the
jnp reference and the Pallas kernel (interpret mode on CPU)."""
from __future__ import annotations

import time


from benchmarks.common import csv_row, text_setup


def _time(fn, *args, reps=20, warmup=3):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def run(verbose=True):
    import jax.numpy as jnp
    from repro.core import lsh as lsh_mod
    from repro.core.pv_dbow import corpus_pairs
    from repro.kernels.common import word_major
    from repro.kernels.hamming import ops as hops

    setup = text_setup(tag="wiki")
    corpus, index, model = setup["corpus"], setup["index"], setup["model"]

    corpus_bytes = sum(s.tokens.nbytes for s in corpus.shards)
    raw_vec_bytes = (index.word_vecs.nbytes + index.doc_vecs.nbytes +
                     index.shard_vecs.nbytes)
    csv_row("tab2_index_size", 0.0,
            f"corpus_MB={corpus_bytes/2**20:.1f};"
            f"raw_vectors_MB={raw_vec_bytes/2**20:.2f};"
            f"lsh_index_MB={index.nbytes()/2**20:.2f};"
            f"compression_vs_raw={raw_vec_bytes/max(index.nbytes(),1):.1f}x")
    csv_row("tab2_train_time", setup["train_s"] * 1e6,
            f"pv_dbow_train_s={setup['train_s']:.1f}")

    # PV-DBOW step throughput (pairs/s), jnp vs fused-kernel path
    import jax
    from repro.core.pv_dbow import sgns_step
    from repro.kernels.negsamp.ops import negsamp_step
    pairs = corpus_pairs(corpus)
    cdf = jnp.asarray(pairs.noise_cdf)
    key = jax.random.PRNGKey(0)
    doc_ids = jnp.asarray(pairs.doc_of_token[:4096])
    word_ids = jnp.asarray(pairs.word_of_token[:4096])
    kw = dict(negatives=5, lr=0.01, unit_norm=True, temperature=8.0)
    us_ref = _time(lambda: sgns_step(model, key, doc_ids, word_ids, cdf,
                                     **kw)[1], reps=10)
    us_ker = _time(lambda: negsamp_step(model, key, doc_ids, word_ids, cdf,
                                        **kw)[1], reps=10)
    csv_row("tab2_sgns_step_jnp", us_ref,
            f"pairs_per_s={4096/(us_ref/1e6):,.0f}")
    csv_row("tab2_sgns_step_kernel_interpret", us_ker,
            f"pairs_per_s={4096/(us_ker/1e6):,.0f}")

    # query-time similarity: Hamming over shard signatures
    q = index.shard_sig[:1]
    db = index.shard_sig
    us_jnp = _time(lambda: lsh_mod.hamming_similarity(
        jnp.asarray(q), jnp.asarray(db), index.bits, 8.0))
    db_wm = word_major(db)
    us_kernel = _time(lambda: hops.hamming_similarity(
        jnp.asarray(q), db_wm, index.bits, temperature=8.0))
    csv_row("query_similarity_jnp", us_jnp, f"n_shards={db.shape[0]}")
    csv_row("query_similarity_kernel_interpret", us_kernel,
            f"n_shards={db.shape[0]}")


if __name__ == "__main__":
    run()
