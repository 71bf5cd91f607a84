"""Batched serving driver: prefill a batch of prompts, then decode N tokens
per request with the KV-cache serve path (greedy or temperature sampling).

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.launch import specs
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M


def generate(cfg, params, prompts, gen_len, temperature=0.0, seed=0):
    """prompts (B, P) int32 -> (B, P+gen_len) tokens."""
    b, plen = prompts.shape
    total = plen + gen_len
    batch = {"tokens": prompts}
    if cfg.vision is not None:
        batch["patches"] = jnp.zeros(
            (b, cfg.vision.n_img_tokens, cfg.vision.d_vision),
            jnp.dtype(cfg.compute_dtype))
    if cfg.encoder is not None:
        batch["frames"] = jnp.zeros((b, cfg.encoder.n_frames, cfg.d_model),
                                    jnp.dtype(cfg.compute_dtype))
    logits, pcache = M.prefill(params, cfg, batch)
    cache = M.convert_prefill_cache(cfg, pcache, plen, total)

    dstep = jax.jit(lambda c, t, p: M.decode_step(params, cfg, c, t, p))
    key = jax.random.PRNGKey(seed)
    out = [prompts]
    lg = logits[:, -1, :]
    for t in range(plen - 1, total - 1):
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, lg / temperature, axis=-1)
        else:
            nxt = jnp.argmax(lg, axis=-1)
        nxt = nxt.astype(jnp.int32)[:, None]
        out.append(nxt)
        lg_step, cache = dstep(cache, nxt, jnp.full((b,), t + 1, jnp.int32))
        lg = lg_step[:, 0, :]
    return jnp.concatenate(out, axis=1)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x22b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.reduced else get_config(args.arch)
    cfg = dataclasses.replace(cfg, compute_dtype=args.dtype)
    params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab,
                                 dtype=jnp.int32)
    t0 = time.time()
    tokens = generate(cfg, params, prompts, args.gen,
                      temperature=args.temperature, seed=args.seed)
    dt = time.time() - t0
    print(json.dumps({
        "arch": cfg.name, "batch": args.batch,
        "prompt_len": args.prompt_len, "generated": args.gen,
        "total_shape": list(tokens.shape),
        "tokens_per_s": round(args.batch * args.gen / dt, 2),
        "wall_s": round(dt, 2),
    }))
    print("sample:", tokens[0, -args.gen:].tolist())


if __name__ == "__main__":
    main()
