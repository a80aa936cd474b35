"""In-kernel building blocks shared by the scoring kernels.

The asym, hamming and megascan kernels reduce their similarity tiles
the same two ways, and must do so with the same ops so that their
results agree bit for bit across schedules:

  * ``segsum_block`` — a one-hot MXU dot of a [TB, TM] tile into the
    resident [TB, S] per-segment output, and ``segsum_band_block``, the
    same dot over only the lane-aligned window of W slots that a
    segment-sorted tile touches;
  * ``topk_block`` — a lane bitonic sort of a [TB, TM] tile
    (``bitonic_sort_desc``; Mosaic has no in-kernel ``lax.top_k``)
    whose first K lanes are the tile's top-k candidates.

Both work on values, so they also run outside a kernel (the sort is
jitted: its lane rotation has no eager rule).

Every float32 matmul in the kernels asks for ``F32`` (full float32
contraction).  Left at the default, a TPU may round float32 operands
to bfloat16 on the MXU, and the kernels would drift from their float32
oracles by far more than the tests' tolerances.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jax.lax.Precision.HIGHEST


def segsum_block(tile: jax.Array, seg: jax.Array, out_ref) -> None:
    """Accumulate one [TB, TM] tile into the resident [TB, S] output by
    a one-hot dot against the [1, TM] row -> slot map.  The one-hot is
    [S, TM] and contracted on its lane axis; rows whose slot is out of
    range (padding) have an all-zero column and add exact float zeros."""
    onehot = (jax.lax.broadcasted_iota(
        jnp.int32, (out_ref.shape[1], seg.shape[1]), 0) == seg
              ).astype(jnp.float32)                         # [S, TM]
    out_ref[...] += jax.lax.dot_general(
        tile, onehot, (((1,), (1,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)


def segsum_band_block(tile: jax.Array, seg: jax.Array, out_ref, start,
                      width: int) -> None:
    """``segsum_block`` over the window ``[start, start + width)`` of
    the resident [TB, S] output: the one-hot is [width, TM], its row r
    standing for slot ``start + r``, and the [TB, width] product is
    added into that window alone.  ``start`` is a multiple of 128 (a
    lane-aligned store) and ``start + width <= S``; a row whose slot
    lies outside the window adds nothing, so the window must hold every
    real slot of the tile for the sums to be whole.  Every column left
    out would receive an exact float zero from the full one-hot."""
    start = pl.multiple_of(start, 128)
    onehot = (jax.lax.broadcasted_iota(
        jnp.int32, (width, seg.shape[1]), 0) + start == seg
              ).astype(jnp.float32)                         # [W, TM]
    out_ref[:, pl.ds(start, width)] += jax.lax.dot_general(
        tile, onehot, (((1,), (1,)), ((), ())), precision=F32,
        preferred_element_type=jnp.float32)


def _lane_xor_partner(x: jax.Array, stride: int,
                      from_fwd: jax.Array) -> jax.Array:
    """Value at lane ``l ^ stride`` for every lane, from two lane
    rotations (``pltpu.roll``, the native TPU rotate — Mosaic lowers no
    reverse or gather here).  ``from_fwd`` says which rotation holds
    the partner in each lane; it is derived by rotating the lane iota
    itself, so the result does not depend on the rotate's direction
    convention."""
    tm = x.shape[1]
    return jnp.where(from_fwd, pltpu.roll(x, stride, 1),
                     pltpu.roll(x, tm - stride, 1))


@jax.jit
def bitonic_sort_desc(vals: jax.Array,
                      idx: jax.Array) -> "tuple[jax.Array, jax.Array]":
    """Full bitonic sort of each row of ``vals`` (lane count a power of
    two), descending by value with ties broken by ascending ``idx`` —
    exactly ``jax.lax.top_k``'s order — co-sorting ``idx``.  Runs as
    log2(TM)*(log2(TM)+1)/2 vectorized compare-exchange stages; every
    partner exchange is a pair of lane rotations, never a gather."""
    tb, tm = vals.shape
    assert tm & (tm - 1) == 0, f"lane count {tm} must be a power of two"
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, tm), 1)
    size = 2
    while size <= tm:
        stride = size // 2
        while stride >= 1:
            from_fwd = pltpu.roll(lane, stride, 1) == (lane ^ stride)
            pv = _lane_xor_partner(vals, stride, from_fwd)
            pi = _lane_xor_partner(idx, stride, from_fwd)
            desc = (lane & size) == 0          # block sorts descending
            is_lower = (lane & stride) == 0    # lane is lower of the pair
            take_big = is_lower == desc
            cur_big = (vals > pv) | ((vals == pv) & (idx < pi))
            keep = take_big == cur_big
            vals = jnp.where(keep, vals, pv)
            idx = jnp.where(keep, idx, pi)
            stride //= 2
        size *= 2
    return vals, idx


def topk_block(tile: jax.Array, valid: jax.Array, base, *,
               k: int) -> "tuple[jax.Array, jax.Array]":
    """One tile's K best (value, global position) candidates: lanes
    where ``valid`` is false are masked to -inf so they can never enter
    a candidate set, the bitonic sort ranks the tile, and the first K
    lanes are emitted.  ``base`` is the tile's first global position."""
    masked = jnp.where(valid, tile, -jnp.inf)
    pos = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) + base
    svals, spos = bitonic_sort_desc(masked, pos)
    return svals[:, :k], spos[:, :k]
