"""Fused batched asymmetric LSH scoring kernel.

The batched query engine's hot path: score a block of B query vectors
against M packed signatures in one shot.  The kernel fuses the three
stages that the numpy path runs separately —

    proj  = Q_hat @ planes.T          (query-side projection, MXU)
    cos   ~ proj @ signs.T * scale    (sign-matmul against unpacked
                                       stored bits, MXU)
    out   = exp(beta * clip(cos))     (exp-cosine map, VPU)

— so the [M, bits] sign matrix is unpacked tile-by-tile in VMEM and
never materialized in HBM.  See kernels/hamming for the symmetric
(two-sided Hamming) sibling.

Fused reductions go one step further: ``asym_exp_segment_sum`` folds
the doc→shard segment sum into the same tile pass (the [B, M] matrix
never leaves VMEM either) and ``asym_exp_topk`` keeps only per-tile
top-k candidates for ranked retrieval.
"""
from repro.kernels.asym.ops import (  # noqa: F401
    asym_exp_layout_sum,
    asym_exp_segment_sum,
    asym_exp_similarity,
    asym_exp_topk,
)
