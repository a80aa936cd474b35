"""Public jit'd wrappers for the fused batched asym kernels.

Off-TPU the Pallas bodies run in interpret mode; on TPU the same
BlockSpecs compile to Mosaic.  Signatures come word-major and
tile-aligned (``common.WordMajorSigs``, made once per signature set);
query rows are normalized and padded to tile multiples here, so the
kernels never see ragged blocks.  The segment sum reads its slot map
as a ``common.SegmentLayout`` (made once per map by the caller, or
per call here): the segment axis padded to lane multiples, padding docs at an
out-of-range slot so they cannot contribute to any sum, and the window
of slots each row tile reduces into.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.asym import kernel as _k
from repro.kernels.common import (SegmentLayout, WordMajorSigs,
                                  interpret_mode, next_pow2, on_tpu,
                                  pad_rows, segment_layout, sig_tile)


def _prep_queries(query_vecs: jax.Array, tb: int):
    """Unit-normalize + row-pad the query block; returns (q, B, tb)."""
    q = jnp.asarray(query_vecs, jnp.float32)
    if q.ndim == 1:
        q = q[None, :]
    b = q.shape[0]
    tb = min(tb, max(1, b))
    q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
    return pad_rows(q, tb), b, tb


def asym_exp_similarity(query_vecs: jax.Array, db: WordMajorSigs,
                        planes: jax.Array, bits: int,
                        *, tb: int = 8, tm: int = 256,
                        temperature: float = 1.0) -> jax.Array:
    """[B, dim] queries x M packed signatures -> [B, M] float32
    exp(temperature * asym-cos).  Queries may have any norm; rows are
    unit-normalized before projection (padding rows stay zero — their
    projections are zero, and the padded outputs are sliced away)."""
    q, b, tb = _prep_queries(query_vecs, tb)
    out = _k.asym_similarity_kernel(
        q, jnp.asarray(planes, jnp.float32), db.t, bits,
        tb=tb, tm=sig_tile(db, tm), interpret=interpret_mode(),
        temperature=temperature)
    return out[:b, :db.n]


def asym_exp_segment_sum(query_vecs: jax.Array, db: WordMajorSigs,
                         planes: jax.Array, bits: int, seg_ids: jax.Array,
                         n_segments: int,
                         *, tb: int = 8, tm: int = 256,
                         temperature: float = 1.0) -> jax.Array:
    """Fused scoring + reduction: [B, dim] x M signatures -> [B, n_segments]
    sums of exp(temperature * asym-cos) grouped by ``seg_ids`` (the
    doc -> segment slot map, int, [M]).  The [B, M] similarity matrix
    stays in VMEM tile-by-tile and never reaches HBM.

    The map is laid out here, on every call; a caller that scores
    against one map many times lays it out once and calls
    ``asym_exp_layout_sum``."""
    return asym_exp_layout_sum(
        query_vecs, db, planes, bits,
        segment_layout(seg_ids, n_segments, db, tm), n_segments, tb=tb,
        temperature=temperature)


def asym_exp_layout_sum(query_vecs: jax.Array, db: WordMajorSigs,
                        planes: jax.Array, bits: int, layout: SegmentLayout,
                        n_segments: int,
                        *, tb: int = 8,
                        temperature: float = 1.0) -> jax.Array:
    """``asym_exp_segment_sum`` over a slot map already laid out over
    ``db`` (``common.segment_layout``; its tile is the row tile).  Rows
    of ``db`` should be segment-sorted: each TM tile then reduces into
    a narrow window of slots, and the kernel contracts only that
    window.  Correctness holds for any order (the window then spans
    every slot).  The output is sliced back to ``n_segments`` here."""
    q, b, tb = _prep_queries(query_vecs, tb)
    out = _k.asym_segment_sum_kernel(
        q, jnp.asarray(planes, jnp.float32), db.t, layout.seg, bits,
        layout.s_pad, starts=layout.starts, width=layout.width, tb=tb,
        tm=layout.seg.shape[1] // layout.starts.shape[0],
        interpret=interpret_mode(), temperature=temperature)
    return out[:b, :n_segments]


def asym_exp_topk(query_vecs: jax.Array, db: WordMajorSigs,
                  planes: jax.Array, bits: int, k: int,
                  *, tb: int = 8, tm: int = 256,
                  temperature: float = 1.0,
                  pad_lanes: "bool | None" = None,
                  ) -> "tuple[jax.Array, jax.Array]":
    """Fused scoring + ranked reduction: returns ([B, k] int32 doc
    indices, [B, k] float32 values), each row sorted by descending
    exp(temperature * asym-cos).  Stage 1 (in-kernel) keeps only the
    per-tile top-k; stage 2 reduces the [B, ceil(M/TM)*kp] candidate
    set — the full [B, M] matrix never reaches HBM.

    K is the kernel's output-block lane width, so on TPU it is padded
    here to a multiple of the 128-lane registers (``kp``) and the
    final top-k slices back to the caller's k — Mosaic then always
    sees aligned [TB, kp] stores (hardware tile shapes reject ragged
    K).  The padding only widens the per-tile candidate sets, a
    superset of the unpadded candidates, so results are unchanged —
    but it is real extra work, so interpret mode (which tolerates
    ragged K) skips it; ``pad_lanes`` overrides the default for
    parity tests of the padded shape off-TPU.  The tile width is a
    power of two (the in-kernel bitonic sort); padding docs are masked
    to -inf and never become candidates."""
    q, b, tb = _prep_queries(query_vecs, tb)
    k = min(int(k), db.n)
    if pad_lanes is None:
        pad_lanes = on_tpu()
    kp = -(-k // 128) * 128 if pad_lanes else k
    # a tile must hold kp candidates
    tm = sig_tile(db, max(next_pow2(tm), next_pow2(kp)))
    vals, idx = _k.asym_topk_kernel(
        q, jnp.asarray(planes, jnp.float32), db.t, bits, kp, db.n,
        tb=tb, tm=tm, interpret=interpret_mode(), temperature=temperature)
    vals, idx = vals[:b], idx[:b]
    top_vals, pos = jax.lax.top_k(vals, k)
    top_idx = jnp.take_along_axis(idx, pos, axis=1)
    return top_idx, top_vals
