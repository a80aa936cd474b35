"""Pallas TPU kernel: spherical k-means assignment step.

The allocation policy (paper Sec. IV-D) clusters document vectors by
cosine.  The assignment step is a dense [dim, N] x [K, dim] matmul
followed by an argmax over centroids — MXU work, fused here so the
score tile never leaves VMEM.

Tiling: rows of x are tiled TN at a time; the centroid matrix is kept
whole in VMEM (K <= ~4096 at dim 128 is ~2 MB fp32).

HARDWARE ADAPTATION note: the score tile is computed transposed,
[K, TN] — centroids on sublanes, documents on lanes — so the argmax
over centroids is a sublane reduction whose [1, TN] result is already
lane-dense and stores straight into a [1, N] output row.  (A 1-D [N]
output block would have to match XLA's 1024-element tiling of the
array, and an argmax over lanes yields a sublane vector that does not
store into one.)  The argmax is written as max + min-index-of-max,
which returns the lowest index among ties, exactly as ``jnp.argmax``.
Centroid rows past ``k_valid`` (sublane padding) never win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import F32


def _assign_kernel(x_ref, c_ref, out_ref, score_ref, *, k_valid: int):
    x = x_ref[...]            # [TN, dim]
    c = c_ref[...]            # [K, dim]
    scores = jax.lax.dot_general(                      # [K, TN] on the MXU
        c, x, (((1,), (1,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    scores = jnp.where(row < k_valid, scores, -jnp.inf)
    best = jnp.max(scores, axis=0, keepdims=True)      # [1, TN]
    out_ref[...] = jnp.min(jnp.where(scores == best, row, scores.shape[0]),
                           axis=0, keepdims=True)
    score_ref[...] = best


@functools.partial(jax.jit, static_argnames=("k_valid", "tn", "interpret"))
def assign_kernel(
    x: jax.Array,     # [N, dim] unit rows
    c: jax.Array,     # [K, dim] unit rows (K may carry padding rows)
    *,
    k_valid: int,     # real centroid count; rows past it never win
    tn: int = 512,
    interpret: bool = False,
):
    """Returns (assignment int32 [1, N], best_score float32 [1, N])."""
    n, dim = x.shape
    k = c.shape[0]
    grid = (pl.cdiv(n, tn),)
    return pl.pallas_call(
        functools.partial(_assign_kernel, k_valid=int(k_valid)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, dim), lambda i: (i, 0)),
            pl.BlockSpec((k, dim), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tn), lambda i: (0, i)),
            pl.BlockSpec((1, tn), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, c)
