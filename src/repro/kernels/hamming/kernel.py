"""Pallas TPU kernel: packed-bit Hamming distance / exp-cosine similarity.

This is the paper's query-time hot spot (Sec. III-B): similarity between
LSH signatures computed as XOR + popcount.  On TPU the packed uint32
lanes live in VMEM and the XOR/popcount run on the VPU; one grid step
processes a (TN x TM) tile of the (queries x items) distance matrix with
the W packed words unrolled into the tile.

Layout choices (HARDWARE ADAPTATION note):
  * query signatures are [N, W] uint32 with W = bits/32; stored
    signatures are *word-major* [W, M] (see kernels/asym for why), so
    the item axis is the lane axis, tiled to TM=512 lanes — a multiple
    of the 128-lane VPU registers and small enough that TN*TM*W stays
    << VMEM.  Each of the W steps XORs a query column [TN, 1] against a
    stored word row [1, TM].
  * popcount is jax.lax.population_count (native TPU op), summed over W
    in registers — no intermediate [TN, TM, W] tensor is materialized in
    HBM, which is the whole point of fusing here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import segsum_block


def _distance_tile(q: jax.Array, db_t: jax.Array) -> jax.Array:
    """[TN, TM] int32: sum_w popcount(q[i, w] ^ db_t[w, j])."""
    acc = jnp.zeros((q.shape[0], db_t.shape[1]), jnp.int32)
    for k in range(q.shape[1]):  # W is tiny (bits/32); unrolled in-register
        x = q[:, k:k + 1] ^ db_t[k:k + 1, :]                # [TN, TM] uint32
        acc = acc + jax.lax.population_count(x).astype(jnp.int32)
    return acc


def sim_tile(q: jax.Array, db_t: jax.Array, bits: float,
             temperature: float) -> jax.Array:
    """[TN, TM] exp(beta*cos(pi*m/L)) tile — the shared fusion core
    (the megascan kernels call it too)."""
    m = _distance_tile(q, db_t).astype(jnp.float32)
    return jnp.exp(temperature * jnp.cos(jnp.pi * m / bits))


def _distance_kernel(q_ref, db_ref, out_ref):
    """One (TN, TM) tile: out[i, j] = sum_w popcount(q[i, w] ^ db[w, j])."""
    out_ref[...] = _distance_tile(q_ref[...], db_ref[...])


def _similarity_kernel(q_ref, db_ref, out_ref, *, bits: float,
                       temperature: float):
    """Fused variant also applying the paper's exp(beta*cos(pi*m/L)) map."""
    out_ref[...] = sim_tile(q_ref[...], db_ref[...], bits, temperature)


def _segsum_similarity_kernel(q_ref, db_ref, seg_ref, out_ref, *,
                              bits: float, temperature: float):
    """Fused similarity + segment reduction (the doc-granular serving
    path): each (TN, TM) similarity tile is reduced into the resident
    [TN, S] output by a one-hot matmul against the doc→shard-slot map.
    The output block's index map ignores the M grid axis, so it stays
    in VMEM and accumulates over all ceil(M/TM) steps — the [N, M]
    similarity matrix never reaches HBM.  Padding docs carry an
    out-of-range slot and contribute to nothing.

    VMEM budget per step (TN=8, TM=512, W=8, S<=2048): tiles + one-hot
    [S, TM] f32 4 MiB < the 16 MiB scoped VMEM of a v5e core."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile = sim_tile(q_ref[...], db_ref[...], bits, temperature)
    segsum_block(tile, seg_ref[...], out_ref)


def _tiled_call(kernel_fn, q, db_t, out_dtype, tn: int, tm: int,
                interpret: bool):
    n, w = q.shape
    w2, m = db_t.shape
    assert w == w2, (w, w2)
    grid = (pl.cdiv(n, tn), pl.cdiv(m, tm))
    return pl.pallas_call(
        kernel_fn,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, tm), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tn, tm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), out_dtype),
        interpret=interpret,
    )(q, db_t)


@functools.partial(jax.jit, static_argnames=("tn", "tm", "interpret"))
def hamming_distance_kernel(
    q_packed: jax.Array,
    db_t: jax.Array,
    *,
    tn: int = 8,
    tm: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """[N, W] x [W, M] uint32 -> [N, M] int32."""
    return _tiled_call(_distance_kernel, q_packed, db_t, jnp.int32,
                       tn, tm, interpret)


@functools.partial(jax.jit, static_argnames=("bits", "tn", "tm", "interpret",
                                             "temperature"))
def hamming_similarity_kernel(
    q_packed: jax.Array,
    db_t: jax.Array,
    bits: int,
    *,
    tn: int = 8,
    tm: int = 512,
    interpret: bool = False,
    temperature: float = 1.0,
) -> jax.Array:
    """[N, W] x [W, M] uint32 -> [N, M] float32 exp(beta*cos(pi*m/bits))."""
    kernel = functools.partial(_similarity_kernel, bits=float(bits),
                               temperature=float(temperature))
    return _tiled_call(kernel, q_packed, db_t, jnp.float32,
                       tn, tm, interpret)


@functools.partial(jax.jit, static_argnames=("bits", "n_segments", "tn", "tm",
                                             "interpret", "temperature"))
def hamming_segment_similarity_kernel(
    q_packed: jax.Array,     # [N, W] uint32
    db_t: jax.Array,         # [W, M] uint32, columns segment-sorted
    seg_ids: jax.Array,      # [1, M] int32 doc -> segment slot
    bits: int,
    n_segments: int,         # S (lane-padded by the ops wrapper)
    *,
    tn: int = 8,
    tm: int = 512,
    interpret: bool = False,
    temperature: float = 1.0,
) -> jax.Array:
    """[N, W] x [W, M] -> [N, S] segment sums of exp(beta*cos(pi*m/L)).

    The M axis is the innermost grid dimension; the output block index
    map ignores it, so each [TN, S] block accumulates in VMEM across
    the whole M sweep (classic K-reduction matmul layout)."""
    n, w = q_packed.shape
    w2, m = db_t.shape
    assert w == w2, (w, w2)
    kernel = functools.partial(_segsum_similarity_kernel, bits=float(bits),
                               temperature=float(temperature))
    grid = (pl.cdiv(n, tn), pl.cdiv(m, tm))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, tm), lambda i, j: (0, j)),
            pl.BlockSpec((1, tm), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tn, n_segments), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n_segments), jnp.float32),
        interpret=interpret,
    )(q_packed, db_t, seg_ids)
