"""Pallas TPU kernel: fused batched asymmetric LSH exp-similarity.

Asymmetric scoring (core/lsh.py ``asymmetric_cosine``) quantizes only
the *stored* side of the similarity: for unit query q and Gaussian
hyperplanes r_i,

    cos(q, s) ~= sum_i (2 b_i(s) - 1) (r_i . q) / (L sqrt(2/pi))

For a batch of B queries this is a [B, bits] x [bits, M] GEMM — the
single-query path's B GEMVs collapsed into one MXU pass.  One grid step
handles a (TB x TM) tile of the (queries x items) output:

  * queries arrive as a [TB, dim] fp32 tile (rows pre-normalized by the
    ops wrapper) and the full [bits, dim] plane matrix sits in VMEM —
    the projection runs on the MXU per tile (bits, dim are both small,
    so recomputing beats an extra HBM round-trip for a [B, bits]
    intermediate);
  * stored signatures arrive packed and *word-major*, a [W, TM] uint32
    block of the [W, M] signature matrix (``db_t``), and are unpacked
    to a [bits, TM] ±1 sign block in-register (sublane broadcast +
    shift/mask on the VPU), never touching HBM at [bits, M] width;
  * the sign-matmul + clip + exp(beta * cos) all fuse into the same
    tile before the single [TB, TM] store.

HARDWARE ADAPTATION note: signatures are word-major because a
row-major [M, W] uint32 array (W = bits/32 = 8 at 256 bits) puts W on
the 128-lane axis: XLA pads it 16x in HBM, and a DMA of a [TM, W]
row slice is not lane-aligned, which Mosaic refuses.  Word-major
blocks are dense (W sublanes x TM lanes), the unpacked signs come out
as the [K, N] right operand the MXU wants, and every shift/compare
runs on int32/uint32 (Mosaic has no uint32 -> float32 cast).  TM
defaults to 256 lanes (multiple of the 128-lane VPU registers); TB to
8 sublanes.

Fused reductions (the serving hot path): for doc-granular scoring the
[B, M] similarity matrix is only an intermediate — the planner consumes
per-*shard* sums and ranked retrieval consumes a top-k.  Two fused
variants keep that intermediate in VMEM:

  * ``asym_segment_sum_kernel`` reduces each post-exp (TB, TM) tile
    into a [TB, S] segment-sum block via a one-hot matmul against the
    doc→shard-slot map (an MXU pass), accumulating across the M grid
    axis in the output block that stays resident in VMEM.  The one-hot
    is built [S, TM] (slots on sublanes, docs on lanes, the layout the
    [1, TM] slot row broadcasts into) and contracted on its lane axis.
    Docs are expected shard-sorted, so each TM tile's one-hot columns
    hit a narrow band of shard slots; only [B, S] ever reaches HBM.
  * ``asym_topk_kernel`` reduces each tile to its per-tile top-k
    (values + global doc indices) with a lane bitonic sort
    (``bitonic_sort_desc``: Mosaic has no in-kernel ``lax.top_k``);
    the caller does the final top-k over the [B, ceil(M/TM)*K]
    candidates — only those reach HBM.

VMEM budget per grid step (defaults TB=8, TM=256, bits=256, dim<=128,
S<=2048): q 4 KiB + planes 128 KiB + packed db 8 KiB + unpacked signs
256 KiB + one-hot 2 MiB + out 64 KiB — well under the 16 MiB scoped
VMEM of a v5e core.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.blocks import F32, segsum_block, topk_block


def _unpack_signs(db_t: jax.Array, bits: int) -> jax.Array:
    """[W, TM] uint32 -> [bits, TM] float32 in {-1, +1}.

    Bit j of word k is signature bit 32*k + j (the pack_bits layout):
    each word row is broadcast over its 32 sublanes and shifted by the
    sublane's bit position.  The shift table is built with
    broadcasted_iota — 1D iota does not lower on TPU."""
    w, tm = db_t.shape
    words = jnp.broadcast_to(db_t[:, None, :], (w, 32, tm)).reshape(w * 32, tm)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (w * 32, tm), 0) & 31
    b = ((words >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    b = b[:bits].astype(jnp.float32)
    return 2.0 * b - 1.0


def exp_sim_tile(q: jax.Array, planes: jax.Array, db_t: jax.Array,
                 bits: int, temperature: float) -> jax.Array:
    """[TB, TM] exp(beta * cos_asym) tile — the shared fusion core
    (the megascan kernels call it too, so every schedule scores a
    block with the same ops)."""
    proj = jax.lax.dot_general(q, planes, (((1,), (1,)), ((), ())),
                               precision=F32,
                               preferred_element_type=jnp.float32)
    signs = _unpack_signs(db_t, bits)                       # [bits, TM]
    scale = 1.0 / (bits * math.sqrt(2.0 / math.pi))
    cos = jnp.dot(proj, signs, precision=F32,
                  preferred_element_type=jnp.float32) * scale
    cos = jnp.clip(cos, -1.0, 1.0)
    return jnp.exp(temperature * cos)


def _asym_sim_kernel(q_ref, planes_ref, db_ref, out_ref, *, bits: int,
                     temperature: float):
    """One (TB, TM) tile of exp(beta * cos_asym(q, db))."""
    out_ref[...] = exp_sim_tile(q_ref[...], planes_ref[...], db_ref[...],
                                bits, temperature)


def _asym_segsum_kernel(q_ref, planes_ref, db_ref, seg_ref, out_ref, *,
                        bits: int, temperature: float):
    """One (TB, TM) tile reduced into the resident [TB, S] output.

    ``seg_ref`` holds the shard slot of each doc column (out-of-range
    slots for padding docs).  The segment sum is a one-hot matmul: with
    docs shard-sorted the [S, TM] one-hot matrix is a narrow diagonal
    band, but correctness does not depend on the ordering.  The output
    block's index map ignores the M grid axis, so it stays in VMEM and
    accumulates across all ceil(M/TM) steps — the [B, M] intermediate
    never reaches HBM."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile = exp_sim_tile(q_ref[...], planes_ref[...], db_ref[...], bits,
                        temperature)
    segsum_block(tile, seg_ref[...], out_ref)


def _asym_topk_kernel(q_ref, planes_ref, db_ref, vals_ref, idx_ref, *,
                      bits: int, temperature: float, k: int, tm: int,
                      m_total: int):
    """Per-tile top-k: each (TB, TM) tile emits its K best values and
    their *global* doc indices; padding columns are masked to -inf so
    they can never enter the candidate set.  The caller runs the final
    top-k over the [B, ceil(M/TM)*K] candidates.  TM must be a power of
    two (the bitonic network)."""
    j = pl.program_id(1)
    tile = exp_sim_tile(q_ref[...], planes_ref[...], db_ref[...], bits,
                        temperature)
    col = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) + j * tm
    vals, idx = topk_block(tile, col < m_total, j * tm, k=k)
    vals_ref[...] = vals
    idx_ref[...] = idx


@functools.partial(jax.jit, static_argnames=("bits", "tb", "tm", "interpret",
                                             "temperature"))
def asym_similarity_kernel(
    q: jax.Array,            # [B, dim] float32, rows unit-normalized
    planes: jax.Array,       # [bits, dim] float32
    db_t: jax.Array,         # [W, M] uint32, word-major signatures
    bits: int,
    *,
    tb: int = 8,
    tm: int = 256,
    interpret: bool = False,
    temperature: float = 1.0,
) -> jax.Array:
    """[B, dim] x [W, M] -> [B, M] float32 exp(beta * asym-cos)."""
    b, dim = q.shape
    w, m = db_t.shape
    assert w * 32 >= bits, (w, bits)
    kernel = functools.partial(_asym_sim_kernel, bits=int(bits),
                               temperature=float(temperature))
    grid = (pl.cdiv(b, tb), pl.cdiv(m, tm))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, dim), lambda i, j: (i, 0)),
            pl.BlockSpec((planes.shape[0], dim), lambda i, j: (0, 0)),
            pl.BlockSpec((w, tm), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tb, tm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        interpret=interpret,
        name="asym_similarity_kernel",
    )(q, planes, db_t)


@functools.partial(jax.jit, static_argnames=("bits", "n_segments", "tb", "tm",
                                             "interpret", "temperature"))
def asym_segment_sum_kernel(
    q: jax.Array,            # [B, dim] float32, rows unit-normalized
    planes: jax.Array,       # [bits, dim] float32
    db_t: jax.Array,         # [W, M] uint32, columns segment-sorted
    seg_ids: jax.Array,      # [1, M] int32 doc -> segment slot
    bits: int,
    n_segments: int,         # S (lane-padded by the ops wrapper)
    *,
    tb: int = 8,
    tm: int = 256,
    interpret: bool = False,
    temperature: float = 1.0,
) -> jax.Array:
    """[B, dim] x [W, M] -> [B, S] segment sums of exp(beta*asym-cos).

    The M axis is the innermost grid dimension and the output block's
    index map ignores it, so each [TB, S] block accumulates in VMEM
    across the whole M sweep (the classic K-reduction matmul layout)."""
    b, dim = q.shape
    w, m = db_t.shape
    assert w * 32 >= bits, (w, bits)
    kernel = functools.partial(_asym_segsum_kernel, bits=int(bits),
                               temperature=float(temperature))
    grid = (pl.cdiv(b, tb), pl.cdiv(m, tm))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, dim), lambda i, j: (i, 0)),
            pl.BlockSpec((planes.shape[0], dim), lambda i, j: (0, 0)),
            pl.BlockSpec((w, tm), lambda i, j: (0, j)),
            pl.BlockSpec((1, tm), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tb, n_segments), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_segments), jnp.float32),
        interpret=interpret,
        name="asym_segment_sum_kernel",
    )(q, planes, db_t, seg_ids)


@functools.partial(jax.jit, static_argnames=("bits", "k", "m_total", "tb",
                                             "tm", "interpret", "temperature"))
def asym_topk_kernel(
    q: jax.Array,            # [B, dim] float32, rows unit-normalized
    planes: jax.Array,       # [bits, dim] float32
    db_t: jax.Array,         # [W, M] uint32, word-major signatures
    bits: int,
    k: int,
    m_total: int,            # unpadded M (padding cols masked to -inf)
    *,
    tb: int = 8,
    tm: int = 256,
    interpret: bool = False,
    temperature: float = 1.0,
) -> "tuple[jax.Array, jax.Array]":
    """Two-stage fused top-k: returns ([B, J*K] values, [B, J*K] int32
    global doc indices) with J = ceil(M/TM) — per-tile candidates only;
    the ops wrapper runs the cheap final top-k over them.

    HARDWARE ADAPTATION note: K is the output block's lane width and
    must be a multiple of the 128-lane registers for Mosaic to lower
    the [TB, K] stores onto hardware tiles — on TPU the ops wrapper
    (``asym_exp_topk``) lane-pads the caller's k before it reaches
    here; interpret mode has no alignment constraint and skips the
    padding to avoid the extra per-tile work."""
    b, dim = q.shape
    w, m = db_t.shape
    assert w * 32 >= bits, (w, bits)
    assert k <= tm and tm & (tm - 1) == 0, (k, tm)
    kernel = functools.partial(_asym_topk_kernel, bits=int(bits),
                               temperature=float(temperature), k=int(k),
                               tm=int(tm), m_total=int(m_total))
    jm = pl.cdiv(m, tm)
    grid = (pl.cdiv(b, tb), jm)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, dim), lambda i, j: (i, 0)),
            pl.BlockSpec((planes.shape[0], dim), lambda i, j: (0, 0)),
            pl.BlockSpec((w, tm), lambda i, j: (0, j)),
        ],
        out_specs=(
            pl.BlockSpec((tb, k), lambda i, j: (i, j)),
            pl.BlockSpec((tb, k), lambda i, j: (i, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, jm * k), jnp.float32),
            jax.ShapeDtypeStruct((b, jm * k), jnp.int32),
        ),
        interpret=interpret,
        name="asym_topk_kernel",
    )(q, planes, db_t)
