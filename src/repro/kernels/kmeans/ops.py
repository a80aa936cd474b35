"""Public wrapper for the spherical k-means assignment kernel."""
from __future__ import annotations

import jax

from repro.kernels.common import interpret_mode, pad_rows
from repro.kernels.kmeans import kernel as _k


def assign_with_scores(x: jax.Array, c: jax.Array, *, tn: int = 512):
    """(assignment int32 [N], best score float32 [N]).  N is padded to
    the tile multiple and the centroids to a sublane multiple here; a
    tile narrower than N is a lane multiple."""
    n, k = x.shape[0], c.shape[0]
    tn = n if n <= tn else tn
    out, sc = _k.assign_kernel(pad_rows(x, tn), pad_rows(c, 8), k_valid=k,
                               tn=tn, interpret=interpret_mode())
    return out[0, :n], sc[0, :n]


def assign(x: jax.Array, c: jax.Array, *, tn: int = 512) -> jax.Array:
    """Assignment only (int32 [N])."""
    return assign_with_scores(x, c, tn=tn)[0]
