"""Pallas TPU kernel: fused PV-DBOW negative-sampling gradient step.

The offline index cost the paper reports (Table II T-Time, hours of
Gensim) is dominated by the SGNS inner loop: for each (doc, word,
negatives) example,

    pos = sigma(w . d) - 1          grad scale for the positive pair
    neg_k = sigma(w_k . d)          grad scales for the k negatives
    g_d  = pos * w + sum_k neg_k * w_k
    g_w  = pos * d
    g_wk = neg_k * d

A naive jnp implementation materializes [B, K, dim] intermediates in HBM
three times (scores, sigmoid, products).  This kernel fuses the whole
example in VMEM: one grid step loads a TB-row tile of the gathered
embeddings, computes scores/sigmoids in registers, and writes the three
gradient tiles — one HBM round-trip instead of ~four.

The gather/scatter stays outside (XLA's sorted scatter-add is already
optimal on TPU and duplicate-index semantics belong to the caller).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import F32


def _negsamp_kernel(d_ref, w_ref, wn_ref, loss_ref, gd_ref, gw_ref, gwn_ref,
                    *, temperature: float):
    d = d_ref[...]                 # [TB, dim]
    w = w_ref[...]                 # [TB, dim]
    wn = wn_ref[...]               # [TB, K, dim]
    t = temperature

    pos = jnp.sum(w * d, axis=-1, keepdims=True) * t    # [TB, 1]
    neg = jnp.einsum("bkd,bod->bko", wn, d[:, None, :], precision=F32,
                     preferred_element_type=jnp.float32)[..., 0] * t  # [TB, K]

    # loss pieces: softplus(-pos) + sum softplus(neg)
    loss_ref[...] = (jax.nn.softplus(-pos)
                     + jax.nn.softplus(neg).sum(axis=-1, keepdims=True))

    gpos = (jax.nn.sigmoid(pos) - 1.0) * t              # dL/d(w.d)  [TB, 1]
    gneg = jax.nn.sigmoid(neg) * t                      # dL/d(wn.d) [TB, K]

    gd_ref[...] = gpos * w + jnp.einsum(
        "bok,bkd->bod", gneg[:, None, :], wn, precision=F32,
        preferred_element_type=jnp.float32)[:, 0]
    gw_ref[...] = gpos * d
    gwn_ref[...] = gneg[:, :, None] * d[:, None, :]


@functools.partial(jax.jit, static_argnames=("tb", "interpret", "temperature"))
def negsamp_grads_kernel(
    d: jax.Array,    # [B, dim] gathered doc vectors
    w: jax.Array,    # [B, dim] gathered positive word vectors
    wn: jax.Array,   # [B, K, dim] gathered negative word vectors
    *,
    tb: int = 256,
    interpret: bool = False,
    temperature: float = 1.0,
):
    """Returns (loss [B, 1], grad_d [B, dim], grad_w [B, dim],
    grad_wn [B, K, dim]).

    HARDWARE ADAPTATION note: both per-example contractions are written
    as batched matmuls whose operands each keep a non-contracting axis
    (a unit axis where the math has none) — Mosaic cannot express a
    batched dot in which one side has no free axis.  The loss leaves as
    a [B, 1] column: a 1-D [B] block would have to match XLA's
    1024-element tiling of the array, and the per-row loss is a
    sublane vector, not a lane row."""
    b, dim = d.shape
    k = wn.shape[1]
    grid = (pl.cdiv(b, tb),)
    return pl.pallas_call(
        functools.partial(_negsamp_kernel, temperature=temperature),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, dim), lambda i: (i, 0)),
            pl.BlockSpec((tb, dim), lambda i: (i, 0)),
            pl.BlockSpec((tb, k, dim), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
            pl.BlockSpec((tb, dim), lambda i: (i, 0)),
            pl.BlockSpec((tb, dim), lambda i: (i, 0)),
            pl.BlockSpec((tb, k, dim), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, dim), jnp.float32),
            jax.ShapeDtypeStruct((b, dim), jnp.float32),
            jax.ShapeDtypeStruct((b, k, dim), jnp.float32),
        ],
        interpret=interpret,
    )(d, w, wn)
