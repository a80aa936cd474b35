"""Serving driver: batched prefill + decode with a KV/SSM cache.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import model as M


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh()
    max_len = args.prompt_len + args.gen + 8
    import dataclasses
    cfg = dataclasses.replace(cfg, max_seq_len=max(cfg.max_seq_len, max_len))

    with mesh:
        key = jax.random.PRNGKey(0)
        params = M.init_params(cfg, key)
        prompts = jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab_size)
        enc = None
        if cfg.is_encdec:
            frames = jax.random.normal(
                key, (args.batch, cfg.encoder_seq, cfg.d_model))
            enc = M.encode(params, frames, cfg)
        elif cfg.family == "vlm":
            enc = jax.random.normal(
                key, (args.batch, cfg.vision_tokens, cfg.d_model))

        state = M.init_decode_state(cfg, args.batch, max_len, enc=enc)
        prefill_fn = jax.jit(make_prefill_step(cfg))
        decode_fn = jax.jit(make_decode_step(cfg))

        t0 = time.time()
        logits, state = prefill_fn(params, prompts, state)
        logits.block_until_ready()
        t_prefill = time.time() - t0

        tok = jnp.argmax(logits, axis=-1)[:, None]
        out_tokens = [tok]
        t0 = time.time()
        for i in range(args.gen - 1):
            logits, state = decode_fn(params, tok, state)
            if args.temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(
                    sub, logits / args.temperature, axis=-1)[:, None]
            else:
                tok = jnp.argmax(logits, axis=-1)[:, None]
            out_tokens.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0

        gen = np.asarray(jnp.concatenate(out_tokens, axis=1))
        print(f"[serve] arch={cfg.name} batch={args.batch} "
              f"prefill {args.prompt_len} tok in {t_prefill*1e3:.0f}ms; "
              f"decode {args.gen} tok in {t_decode*1e3:.0f}ms "
              f"({args.batch*(args.gen-1)/max(t_decode,1e-9):.0f} tok/s)")
        print(f"[serve] first sequence: {gen[0][:16].tolist()} ...")


if __name__ == "__main__":
    main()
