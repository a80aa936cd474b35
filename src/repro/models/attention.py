"""GQA attention: training (full sequence), prefill, and cached decode.

Design notes (TPU adaptation):
  * GQA is computed by reshaping query heads into [kv_heads, group] so
    the einsum contracts against un-repeated K/V — no materialized
    repeat_kv, which matters when kv_heads << heads (starcoder2 kv=2).
  * ``attn_impl="chunked"`` is a flash-style lazy-softmax over KV chunks
    (running max/denominator) — the sub-quadratic-memory path used by
    long sequences; "dense" materializes [B, H, S, S] and is fine at
    train_4k.
  * Decode: one query token against a [B, S_max, kv, hd] cache with a
    position mask; cache layout keeps seq minor-adjacent to heads so the
    update is a dynamic_update_slice on a contiguous block.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_constraint
from repro.models.layers import apply_rope

NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: [B, S, KH, G, hd], k: [B, T, KH, hd] -> [B, KH, G, S, T]."""
    return jnp.einsum("bskgd,btkd->bkgst", q, k)


def _gqa_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: [B, KH, G, S, T], v: [B, T, KH, hd] -> [B, S, KH, G, hd]."""
    return jnp.einsum("bkgst,btkd->bskgd", p, v)


def _causal_mask(s: int, t: int, offset: int = 0,
                 window: int = 0) -> jax.Array:
    """[S, T] True = visible.  offset positions precede the queries."""
    qpos = jnp.arange(s)[:, None] + offset
    kpos = jnp.arange(t)[None, :]
    mask = kpos <= qpos
    if window > 0:
        mask &= kpos > (qpos - window)
    return mask


def dense_attention(
    q: jax.Array,              # [B, S, H, hd]
    k: jax.Array,              # [B, T, KH, hd]
    v: jax.Array,              # [B, T, KH, hd]
    *,
    causal: bool,
    window: int = 0,
    q_offset: int = 0,
    kv_valid_len: Optional[jax.Array] = None,   # [B] for decode masking
) -> jax.Array:
    b, s, h, hd = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    qg = q.reshape(b, s, kh, g, hd)
    scores = _gqa_scores(qg, k) / jnp.sqrt(hd).astype(q.dtype)
    mask = None
    if causal:
        mask = _causal_mask(s, t, q_offset, window)[None, None, None]
    if kv_valid_len is not None:
        valid = jnp.arange(t)[None, :] < kv_valid_len[:, None]   # [B, T]
        valid = valid[:, None, None, None, :]
        mask = valid if mask is None else (mask & valid)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = _gqa_out(p, v)
    return out.reshape(b, s, h, hd)


def chunked_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    q_offset: int = 0,
    chunk: int = 512,
    unroll: bool = False,
) -> jax.Array:
    """Flash-style lazy softmax over KV chunks: O(S * chunk) live scores."""
    b, s, h, hd = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    qg = q.reshape(b, s, kh, g, hd)
    n_chunks = (t + chunk - 1) // chunk
    pad = n_chunks * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, kh, hd)
    vc = v.reshape(b, n_chunks, chunk, kh, hd)
    scale = 1.0 / jnp.sqrt(hd)

    def _pin(m, denom, acc):
        # Pin the scan carry's sharding: unconstrained, GSPMD replicates
        # the fp32 accumulator (measured 21.5 GiB/device at qwen
        # prefill_32k).  Query-seq shards over "model" (context
        # parallelism) because kv-head counts rarely divide the axis.
        m = shard_constraint(m, "batch", "kv_heads", None, "attn_q_seq")
        denom = shard_constraint(denom, "batch", "kv_heads", None, "attn_q_seq")
        acc = shard_constraint(acc, "batch", "kv_heads", None,
                               "attn_q_seq", None)
        return m, denom, acc

    def body(carry, inputs):
        m, denom, acc = carry                  # running max / denom / numerator
        kci, vci, ci = inputs
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, kci) * scale
        scores = shard_constraint(scores, "batch", "kv_heads", None,
                                  "attn_q_seq", None)
        kpos = ci * chunk + jnp.arange(chunk)[None, :]
        qpos = jnp.arange(s)[:, None] + q_offset
        mask = kpos < t + 0 * kpos             # drop the zero-padding
        if causal:
            mask = mask & (kpos <= qpos)
            if window > 0:
                mask = mask & (kpos > qpos - window)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = denom * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bkgst,btkd->bkgsd", p, vci)
        return _pin(m_new, l_new, acc_new), None

    m0 = jnp.full((b, kh, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, s), jnp.float32)
    acc0 = jnp.zeros((b, kh, g, s, hd), jnp.float32)
    m0, l0, acc0 = _pin(m0, l0, acc0)
    # unroll=True flattens the chunk loop (used by the dry-run cost
    # probes: cost_analysis counts while bodies once)
    (m, denom, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (kc.swapaxes(0, 1), vc.swapaxes(0, 1), jnp.arange(n_chunks)),
        unroll=n_chunks if unroll else 1)
    out = acc / jnp.maximum(denom[..., None], 1e-20)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd).astype(q.dtype)


class KVCache(NamedTuple):
    """Ring-buffer KV cache.

    ``pos[s]`` is the absolute token position stored in slot ``s`` (-1 =
    empty).  Full-attention models allocate S_max >= total length, so the
    ring never wraps; sliding-window models allocate S_max = window and
    the ring semantics give an O(window) decode state (what qualifies
    hymba for long_500k)."""
    k: jax.Array          # [B, S_max, KH, hd]
    v: jax.Array          # [B, S_max, KH, hd]
    pos: jax.Array        # [S_max] int32 absolute positions, -1 empty
    length: jax.Array     # [] int32 — tokens seen so far


def init_kv_cache(batch: int, max_seq: int, kv_heads: int, head_dim: int,
                  dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, max_seq, kv_heads, head_dim), dtype),
        v=jnp.zeros((batch, max_seq, kv_heads, head_dim), dtype),
        pos=jnp.full((max_seq,), -1, jnp.int32),
        length=jnp.zeros((), jnp.int32),
    )


def cache_pos_update(pos: jax.Array, length: jax.Array, s_new: int) -> jax.Array:
    """Position-buffer half of cache_update (shared across layers)."""
    s_max = pos.shape[0]
    if s_new >= s_max:
        tail_pos = length + jnp.arange(s_new - s_max, s_new)
        shift = tail_pos[0] % s_max
        return jnp.roll(tail_pos, shift).astype(jnp.int32)
    new_pos = length + jnp.arange(s_new)
    slots = (new_pos % s_max).astype(jnp.int32)
    return pos.at[slots].set(new_pos.astype(jnp.int32))


def cache_update(cache: KVCache, k_new: jax.Array, v_new: jax.Array) -> KVCache:
    """Append S_new tokens starting at absolute position cache.length.
    Slots wrap modulo S_max (ring buffer); if S_new >= S_max only the
    last S_max tokens are kept."""
    s_max = cache.k.shape[1]
    s_new = k_new.shape[1]
    pos = cache_pos_update(cache.pos, cache.length, s_new)
    if s_new >= s_max:
        # keep only the tail; lay it out so slot == pos % s_max
        tail_pos = cache.length + jnp.arange(s_new - s_max, s_new)
        k_tail = k_new[:, -s_max:].astype(cache.k.dtype)
        v_tail = v_new[:, -s_max:].astype(cache.v.dtype)
        shift = tail_pos[0] % s_max
        k = jnp.roll(k_tail, shift, axis=1)
        v = jnp.roll(v_tail, shift, axis=1)
        return KVCache(k, v, pos, cache.length + s_new)
    new_pos = cache.length + jnp.arange(s_new)
    slots = (new_pos % s_max).astype(jnp.int32)
    k = cache.k.at[:, slots].set(k_new.astype(cache.k.dtype))
    v = cache.v.at[:, slots].set(v_new.astype(cache.v.dtype))
    return KVCache(k, v, pos, cache.length + s_new)


def attention_apply(
    p: dict,                       # attn params
    x: jax.Array,                  # [B, S, d_model]
    *,
    cfg,
    positions: jax.Array,          # [B, S] or [S]
    cache: Optional[KVCache] = None,
    causal: bool = True,
    window: int = 0,
    use_rope: bool = True,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Self-attention with optional KV cache (decode/prefill)."""
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    k = jnp.einsum("bsd,dk->bsk", x, p["wk"])
    v = jnp.einsum("bsd,dk->bsk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    # TP over heads when the head count divides the model axis;
    # otherwise sequence parallelism (seq always divides our shapes).
    # An explicit None constraint REPLICATES the dim — measured 3.7x
    # per-device HLO flops on smollm (15 heads on a 16-way axis) when
    # attention fell back to replication.
    from repro.distributed.sharding import mesh_axis_size
    msize = mesh_axis_size("model")
    heads_divide = bool(msize) and cfg.n_heads % msize == 0 and \
        cfg.n_kv_heads % msize == 0
    if msize is None or heads_divide:
        q = shard_constraint(q, "batch", "seq", "heads", None)
        k = shard_constraint(k, "batch", "seq", "kv_heads", None)
        v = shard_constraint(v, "batch", "seq", "kv_heads", None)
    elif s > 1:
        q = shard_constraint(q, "batch", "attn_q_seq", None, None)
        k = shard_constraint(k, "batch", "attn_q_seq", None, None)
        v = shard_constraint(v, "batch", "attn_q_seq", None, None)
    if use_rope:
        if positions.ndim == 1:
            positions = positions[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        new_cache = cache_update(cache, k, v)
        if s > 1:
            # prefill: queries attend over the fresh K/V directly (the
            # ring buffer may hold only the window tail, which would be
            # wrong for early queries); cache starts empty in this flow.
            if cfg.attn_impl == "chunked":
                out = chunked_attention(q, k, v, causal=causal, window=window,
                                        unroll=not cfg.scan_layers)
            else:
                out = dense_attention(q, k, v, causal=causal, window=window)
        else:
            out = _decode_attention(q, new_cache, window=window)
    elif cfg.attn_impl == "chunked":
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                unroll=not cfg.scan_layers)
    else:
        out = dense_attention(q, k, v, causal=causal, window=window)

    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = jnp.einsum("bsq,qd->bsd", out, p["wo"])
    return out, new_cache


def _decode_attention(q, cache: KVCache, *, window: int) -> jax.Array:
    """One-token attention against the ring buffer: slot validity and
    causality come from the stored absolute positions."""
    b, s, h, hd = q.shape
    kh = cache.k.shape[2]
    g = h // kh
    qg = q.reshape(b, s, kh, g, hd)
    scores = _gqa_scores(qg, cache.k.astype(q.dtype)) / jnp.sqrt(hd).astype(q.dtype)
    qpos = cache.length - 1                       # position of the new token
    kpos = cache.pos[None, :]                     # [1, S_max]
    mask = (kpos >= 0) & (kpos <= qpos)
    if window > 0:
        mask = mask & (kpos > qpos - window)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = _gqa_out(p, cache.v.astype(q.dtype))
    return out.reshape(b, s, h, hd)


def cross_attention_apply(
    p: dict,
    x: jax.Array,                  # [B, S, d_model] decoder side
    enc: jax.Array,                # [B, T, d_model] encoder / vision side
    *,
    cfg,
) -> jax.Array:
    b, s, _ = x.shape
    t = enc.shape[1]
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(
        b, s, cfg.n_heads, cfg.head_dim)
    k = jnp.einsum("btd,dk->btk", enc, p["wk"]).reshape(
        b, t, cfg.n_kv_heads, cfg.head_dim)
    v = jnp.einsum("btd,dk->btk", enc, p["wv"]).reshape(
        b, t, cfg.n_kv_heads, cfg.head_dim)
    out = dense_attention(q, k, v, causal=False)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return jnp.einsum("bsq,qd->bsd", out, p["wo"])
