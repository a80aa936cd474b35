"""Public wrappers: fused SGNS gradients + a full PV-DBOW SGD step.

``negsamp_step`` has the same signature/semantics as
``repro.core.pv_dbow.sgns_step`` so the trainer can swap paths with the
``use_kernel`` config flag; scatter-adds with duplicate-index addition
semantics are done with ``.at[].add`` (XLA scatter-add) outside the
kernel.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.common import interpret_mode, pad_rows
from repro.kernels.negsamp import kernel as _k


def negsamp_grads(d: jax.Array, w: jax.Array, wn: jax.Array, *, tb: int = 256,
                  temperature: float = 1.0):
    """(loss [B], grad_d [B, dim], grad_w [B, dim], grad_wn [B, K, dim])."""
    b = d.shape[0]
    tb = b if b <= tb else tb
    loss, gd, gw, gwn = _k.negsamp_grads_kernel(
        pad_rows(d, tb), pad_rows(w, tb), pad_rows(wn, tb), tb=tb,
        interpret=interpret_mode(), temperature=temperature)
    return loss[:b, 0], gd[:b], gw[:b], gwn[:b]


@functools.partial(jax.jit, static_argnames=("negatives", "lr", "unit_norm", "temperature"))
def negsamp_step(
    model,                       # PVDBOWModel(word_vecs, doc_vecs)
    key: jax.Array,
    doc_ids: jax.Array,          # int32 [B]
    word_ids: jax.Array,         # int32 [B]
    noise_cdf: jax.Array,        # [V] cumulative noise distribution
    *,
    negatives: int,
    lr: float,
    unit_norm: bool,
    temperature: float = 1.0,
):
    from repro.core.pv_dbow import PVDBOWModel, _unit_rows, sample_negatives

    b = doc_ids.shape[0]
    neg_ids = sample_negatives(key, noise_cdf, (b, negatives))

    d = model.doc_vecs[doc_ids]
    w = model.word_vecs[word_ids]
    wn = model.word_vecs[neg_ids]
    loss, gd, gw, gwn = negsamp_grads(d, w, wn, temperature=temperature)

    # sum-reduction semantics (matches sgns_loss): per-row O(1) updates
    new_d = model.doc_vecs.at[doc_ids].add(-lr * gd)
    new_w = model.word_vecs.at[word_ids].add(-lr * gw)
    new_w = new_w.at[neg_ids.reshape(-1)].add(
        -lr * gwn.reshape(-1, gwn.shape[-1]))
    if unit_norm:
        new_w = _unit_rows(new_w)
        new_d = _unit_rows(new_d)
    return PVDBOWModel(new_w, new_d), loss.mean()
