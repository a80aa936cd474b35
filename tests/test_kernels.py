"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU per the harness contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.asym import ops as aops
from repro.kernels.asym import ref as aref
from repro.kernels.common import word_major
from repro.kernels.hamming import ops as hops
from repro.kernels.hamming import ref as href
from repro.kernels.kmeans import ops as kops
from repro.kernels.kmeans import ref as kref
from repro.kernels.negsamp import ops as nops
from repro.kernels.negsamp import ref as nref


# ----------------------------------------------------------------------
# hamming
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n,m,words", [
    (1, 7, 4), (3, 512, 4), (8, 513, 8), (5, 64, 2), (16, 1000, 1),
])
def test_hamming_distance_matches_ref(n, m, words):
    rng = np.random.default_rng(n * 100 + m)
    q = jnp.asarray(rng.integers(0, 2**32, (n, words), dtype=np.uint32))
    db = jnp.asarray(rng.integers(0, 2**32, (m, words), dtype=np.uint32))
    got = hops.hamming_distance(q, word_major(db))
    want = href.hamming_distance_ref(q, db)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bits,temp", [(128, 1.0), (128, 8.0), (256, 4.0)])
def test_hamming_similarity_matches_ref(bits, temp):
    rng = np.random.default_rng(bits)
    w = bits // 32
    q = jnp.asarray(rng.integers(0, 2**32, (4, w), dtype=np.uint32))
    db = jnp.asarray(rng.integers(0, 2**32, (300, w), dtype=np.uint32))
    got = hops.hamming_similarity(q, word_major(db), bits, temperature=temp)
    want = href.hamming_similarity_ref(q, db, bits) ** temp
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5)


# ----------------------------------------------------------------------
# asym (fused batched projection + sign-matmul + exp-cosine)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("b,m,dim,bits,temp", [
    (1, 7, 24, 128, 1.0), (5, 300, 48, 256, 8.0), (16, 1000, 32, 64, 4.0),
    (3, 257, 48, 128, 8.0), (9, 512, 64, 96, 2.0),
])
def test_asym_similarity_matches_ref(b, m, dim, bits, temp):
    from repro.core import lsh as lsh_mod
    rng = np.random.default_rng(b * 1000 + m)
    q = jnp.asarray(rng.normal(size=(b, dim)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(m, dim)).astype(np.float32))
    planes = lsh_mod.hyperplanes(lsh_mod.LSHConfig(bits=bits), dim)
    db = lsh_mod.pack_bits(lsh_mod.signature_bits(x, planes))
    got = aops.asym_exp_similarity(q, word_major(db), planes, bits,
                                   temperature=temp)
    want = aref.asym_exp_similarity_ref(q, db, planes, bits, temperature=temp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5)


def test_asym_kernel_matches_single_query_asymmetric_cosine():
    """The fused batch kernel row-matches core asymmetric_cosine."""
    from repro.core import lsh as lsh_mod
    rng = np.random.default_rng(7)
    dim, bits, temp = 48, 128, 8.0
    q = rng.normal(size=(4, dim)).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(200, dim)).astype(np.float32))
    planes = lsh_mod.hyperplanes(lsh_mod.LSHConfig(bits=bits), dim)
    db = lsh_mod.pack_bits(lsh_mod.signature_bits(x, planes))
    got = np.asarray(aops.asym_exp_similarity(
        jnp.asarray(q), word_major(db), planes, bits, temperature=temp))
    for i in range(q.shape[0]):
        cos = lsh_mod.asymmetric_cosine(jnp.asarray(q[i]), db, planes, bits)
        np.testing.assert_allclose(got[i], np.exp(temp * np.asarray(cos)),
                                   rtol=3e-5)


# ----------------------------------------------------------------------
# negsamp
# ----------------------------------------------------------------------
@pytest.mark.parametrize("b,dim,k,temp", [
    (16, 32, 5, 1.0), (100, 64, 3, 8.0), (256, 16, 1, 4.0), (7, 128, 8, 8.0),
])
def test_negsamp_grads_match_ref(b, dim, k, temp):
    rng = np.random.default_rng(b)
    d = jnp.asarray(rng.normal(size=(b, dim)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(b, dim)).astype(np.float32))
    wn = jnp.asarray(rng.normal(size=(b, k, dim)).astype(np.float32))
    got = nops.negsamp_grads(d, w, wn, temperature=temp)
    want = nref.negsamp_grads_ref(d, w, wn, temperature=temp)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)


def test_negsamp_grads_match_autodiff():
    """The fused manual gradients == jax.grad of the loss."""
    rng = np.random.default_rng(9)
    b, dim, k = 32, 24, 4
    d = jnp.asarray(rng.normal(size=(b, dim)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(b, dim)).astype(np.float32))
    wn = jnp.asarray(rng.normal(size=(b, k, dim)).astype(np.float32))

    def loss(d, w, wn):
        pos = jnp.sum(w * d, axis=-1)
        neg = jnp.einsum("bkd,bd->bk", wn, d)
        return (jax.nn.softplus(-pos) + jax.nn.softplus(neg).sum(-1)).sum()

    gd_ad, gw_ad, gwn_ad = jax.grad(loss, argnums=(0, 1, 2))(d, w, wn)
    _, gd, gw, gwn = nops.negsamp_grads(d, w, wn, temperature=1.0)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(gd_ad), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ad), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gwn), np.asarray(gwn_ad), rtol=1e-4, atol=1e-5)


def test_negsamp_step_trains(small_corpus):
    """The kernel-backed step must behave like the reference step."""
    from repro.core.pv_dbow import PVDBOWConfig, train_pv_dbow
    cfg = PVDBOWConfig(dim=16, steps=60, batch_pairs=512, use_kernel=True)
    model = train_pv_dbow(small_corpus, cfg)
    assert np.isfinite(np.asarray(model.word_vecs)).all()
    norms = np.linalg.norm(np.asarray(model.word_vecs), axis=1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-4)


# ----------------------------------------------------------------------
# kmeans
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n,k,dim", [(10, 3, 8), (513, 16, 32), (1000, 7, 64)])
def test_kmeans_assign_matches_ref(n, k, dim):
    rng = np.random.default_rng(n)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    c = rng.normal(size=(k, dim)).astype(np.float32)
    c /= np.linalg.norm(c, axis=1, keepdims=True)
    got = kops.assign(jnp.asarray(x), jnp.asarray(c))
    want, _ = kref.assign_ref(jnp.asarray(x), jnp.asarray(c))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
