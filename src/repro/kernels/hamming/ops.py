"""Public jit'd wrappers for the hamming kernel.

Off-TPU the Pallas body runs in interpret mode; on TPU the same
BlockSpecs compile to Mosaic.  Stored signatures come word-major and
tile-aligned (``common.WordMajorSigs``, made once per signature set);
query signatures are padded to tile multiples here, so the kernel never
sees ragged blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import (WordMajorSigs, interpret_mode, pad_rows,
                                  sig_tile, slot_pad)
from repro.kernels.hamming import kernel as _k


def hamming_distance(q_packed: jax.Array, db: WordMajorSigs,
                     *, tn: int = 8, tm: int = 512) -> jax.Array:
    n = q_packed.shape[0]
    tn = min(tn, max(1, n))
    q = pad_rows(jnp.asarray(q_packed, jnp.uint32), tn)
    out = _k.hamming_distance_kernel(q, db.t, tn=tn, tm=sig_tile(db, tm),
                                     interpret=interpret_mode())
    return out[:n, :db.n]


def hamming_similarity(q_packed: jax.Array, db: WordMajorSigs, bits: int,
                       *, tn: int = 8, tm: int = 512,
                       temperature: float = 1.0) -> jax.Array:
    n = q_packed.shape[0]
    tn = min(tn, max(1, n))
    q = pad_rows(jnp.asarray(q_packed, jnp.uint32), tn)
    out = _k.hamming_similarity_kernel(q, db.t, bits, tn=tn,
                                       tm=sig_tile(db, tm),
                                       interpret=interpret_mode(),
                                       temperature=temperature)
    return out[:n, :db.n]


def hamming_segment_similarity(q_packed: jax.Array, db: WordMajorSigs,
                               bits: int, seg_ids: jax.Array,
                               n_segments: int,
                               *, tn: int = 8, tm: int = 512,
                               temperature: float = 1.0) -> jax.Array:
    """Fused scoring + reduction: [N, W] x M signatures -> [N, n_segments]
    sums of exp(beta*cos(pi*m/L)) grouped by ``seg_ids`` (the doc ->
    segment slot map, int, [M]).  The [N, M] similarity matrix stays
    in VMEM tile-by-tile and never reaches HBM.  Rows of ``db``
    should be segment-sorted so each TM tile reduces into a narrow
    band of slots (correctness holds for any order); padding docs get
    an out-of-range slot and contribute to nothing."""
    n = q_packed.shape[0]
    tn = min(tn, max(1, n))
    q = pad_rows(jnp.asarray(q_packed, jnp.uint32), tn)
    s_pad = slot_pad(n_segments)
    seg = jnp.asarray(seg_ids, jnp.int32).reshape(1, -1)
    seg = jnp.pad(seg, ((0, 0), (0, db.t.shape[1] - db.n)),
                  constant_values=s_pad)
    out = _k.hamming_segment_similarity_kernel(
        q, db.t, seg, bits, s_pad, tn=tn, tm=sig_tile(db, tm),
        interpret=interpret_mode(), temperature=temperature)
    return out[:n, :n_segments]
