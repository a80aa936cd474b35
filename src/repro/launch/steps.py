"""pjit-able step functions + their sharding trees.

``make_train_step`` builds loss -> grad -> (microbatched accumulation)
-> AdamW update; ``make_prefill_step`` / ``make_decode_step`` wrap the
serving entry points.  ``sharding trees`` map every argument/output to
NamedShardings derived from the logical rules, so launch code never
hand-writes PartitionSpecs per architecture.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import logical_to_mesh_spec
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optimizer.adamw import AdamWConfig, OptState, adamw_init, adamw_update
from repro.optimizer.schedules import cosine_warmup_schedule


# ----------------------------------------------------------------------
# sharding trees
# ----------------------------------------------------------------------
def batch_axes(mesh: Mesh, global_batch: int) -> Tuple[str, ...]:
    """Largest prefix of (pod, data) whose product divides the batch."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    chosen: list = []
    prod = 1
    for a in axes:
        size = mesh.shape[a]
        if global_batch % (prod * size) == 0:
            chosen.append(a)
            prod *= size
    return tuple(chosen)


def legalize_sharding(sharding: NamedSharding,
                      shape: Tuple[int, ...]) -> NamedSharding:
    """pjit *argument* shardings must divide each dimension exactly
    (unlike internal with_sharding_constraints, which GSPMD pads).  Drop
    mesh axes that don't divide — e.g. kv_heads=8 on a 16-way model
    axis, or whisper's odd vocab 51865 — leaving that dim replicated.
    The §Perf log tracks where this costs us."""
    mesh = sharding.mesh
    spec = sharding.spec
    new = []
    for i, dim in enumerate(shape):
        ax = spec[i] if i < len(spec) else None
        if ax is None:
            new.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        keep = []
        prod = 1
        for a in axes:
            size = mesh.shape[a]
            if dim % (prod * size) == 0:
                keep.append(a)
                prod *= size
        new.append(tuple(keep) if len(keep) > 1
                   else (keep[0] if keep else None))
    return NamedSharding(mesh, P(*new))


def legalize_tree(shardings, abstract):
    return jax.tree_util.tree_map(
        lambda sh, ab: legalize_sharding(sh, ab.shape)
        if isinstance(sh, NamedSharding) else sh,
        shardings, abstract)


def params_shardings(cfg: ModelConfig, mesh: Mesh, serve: bool = False):
    """Parameter shardings.  ``serve=True`` drops the FSDP axis: with no
    optimizer state to shard, replicating params over `data` removes the
    per-layer all-gathers from every decode step (measured 251 MB x 12
    gathers/step on qwen decode_32k) at a small HBM cost."""
    from repro.distributed.sharding import set_rules
    axes_tree = M.logical_axes(cfg)
    if serve:
        with set_rules({"fsdp": None}):
            raw = jax.tree_util.tree_map(
                lambda ax: NamedSharding(mesh, logical_to_mesh_spec(ax, mesh)),
                axes_tree, is_leaf=lambda x: isinstance(x, tuple))
    else:
        raw = jax.tree_util.tree_map(
            lambda ax: NamedSharding(mesh, logical_to_mesh_spec(ax, mesh)),
            axes_tree, is_leaf=lambda x: isinstance(x, tuple))
    return legalize_tree(raw, abstract_params(cfg))


def opt_state_shardings(cfg: ModelConfig, mesh: Mesh):
    p_sh = params_shardings(cfg, mesh)
    return OptState(
        step=NamedSharding(mesh, P()),
        m=p_sh, v=p_sh)


def batch_shardings(cfg: ModelConfig, mesh: Mesh, global_batch: int,
                    with_enc: bool):
    ba = batch_axes(mesh, global_batch)
    spec2 = NamedSharding(mesh, P(ba if ba else None, None))
    out = {"tokens": spec2, "labels": spec2, "mask": spec2}
    if with_enc:
        out["enc_inputs"] = NamedSharding(mesh, P(ba if ba else None,
                                                  None, None))
    return out


def decode_state_shardings(cfg: ModelConfig, mesh: Mesh,
                           state_abstract: M.DecodeState,
                           global_batch: int) -> M.DecodeState:
    """Sharding tree matching a DecodeState: batch over (pod, data), kv
    heads / ssm heads / d_inner over model, everything else replicated."""
    ba = batch_axes(mesh, global_batch)
    b_ax = ba if ba else None

    def kv_spec(arr):
        # Seq-sharding the cache over "model" (context parallelism):
        # kv_heads (8, 5, 12...) rarely divide a 16-way model axis, but
        # the 32k/500k cache seq always does — this is what takes the
        # decode-cell KV from replicated (48 GiB/chip) to 3 GiB/chip.
        if arr.ndim == 6:    # [G, per, B, S, KH, hd] (vlm / moe groups)
            return P(None, None, b_ax, "model", None, None)
        return P(None, b_ax, "model", None, None)   # [L, B, S, KH, hd]

    kv = None
    if state_abstract.kv is not None:
        kv = jax.tree_util.tree_map(
            lambda a: legalize_sharding(
                NamedSharding(mesh, kv_spec(a)), a.shape),
            state_abstract.kv)
    ssm = None
    if state_abstract.ssm is not None:
        st, cv = state_abstract.ssm
        ssm = (legalize_sharding(
                   NamedSharding(mesh, P(None, b_ax, "model", None, None)),
                   st.shape),
               legalize_sharding(
                   NamedSharding(mesh, P(None, b_ax, None, "model")),
                   cv.shape))
    pos = (NamedSharding(mesh, P(None))
           if state_abstract.pos is not None else None)
    enc = None
    if state_abstract.enc is not None:
        enc = legalize_sharding(NamedSharding(mesh, P(b_ax, None, None)),
                                state_abstract.enc.shape)
    return M.DecodeState(kv=kv, ssm=ssm, pos=pos,
                         length=NamedSharding(mesh, P()), enc=enc)


# ----------------------------------------------------------------------
# step functions
# ----------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    microbatches: int = 1, total_steps: int = 10000,
                    warmup_steps: int = 200,
                    accum_dtype=None):
    """Returns train_step(params, opt_state, batch) -> (params,
    opt_state, metrics).  ``accum_dtype``: gradient-accumulator dtype
    across microbatches (default fp32; bf16 halves the accumulator for
    very large models — fine at mb<=64 summation depth)."""
    acc_dt = accum_dtype or jnp.float32

    def train_step(params, opt_state, batch):
        if microbatches <= 1:
            loss, grads = jax.value_and_grad(M.loss_fn)(params, batch, cfg)
        else:
            def reshape(x):
                b = x.shape[0]
                return x.reshape((microbatches, b // microbatches)
                                 + x.shape[1:])
            mbs = jax.tree_util.tree_map(reshape, batch)

            def body(acc, mb):
                loss, g = jax.value_and_grad(M.loss_fn)(params, mb, cfg)
                acc = jax.tree_util.tree_map(
                    lambda a, gg: a + gg.astype(a.dtype), acc, g)
                return acc, loss
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dt), params)
            grads, losses = jax.lax.scan(body, zeros, mbs)
            grads = jax.tree_util.tree_map(
                lambda g: g / microbatches, grads)
            loss = losses.mean()
        lr_scale = cosine_warmup_schedule(
            opt_state.step, warmup_steps=warmup_steps,
            total_steps=total_steps)
        params, opt_state, metrics = adamw_update(
            params, grads, opt_state, opt_cfg, lr_scale)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, tokens, state):
        return M.prefill(params, tokens, cfg, state)
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, token, state):
        return M.decode_step(params, token, cfg, state)
    return decode_step


def abstract_params(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs without allocation."""
    return jax.eval_shape(
        functools.partial(M.init_params, cfg), jax.random.PRNGKey(0))


def abstract_opt_state(cfg: ModelConfig, opt_cfg: AdamWConfig):
    aparams = abstract_params(cfg)
    return jax.eval_shape(functools.partial(adamw_init, cfg=opt_cfg),
                          aparams)


def abstract_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                          with_enc: bool):
    def build():
        enc = None
        if with_enc:
            t = cfg.encoder_seq if cfg.is_encdec else cfg.vision_tokens
            enc = jnp.zeros((batch, t, cfg.d_model),
                            cfg.dtypes.compute_dtype)
        return M.init_decode_state(cfg, batch, max_len, enc=enc)
    return jax.eval_shape(build)
