"""Batched serving driver: continuous-batching style prefill + decode loop.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \\
      --batch 4 --prompt-len 64 --max-new 32

Serving loop: batch B prompts -> prefill -> greedy decode with a static-shape
KV cache; reports per-phase latency and tokens/s.  The full-scale path lowers
the same `serve_step` the dry-run proves against the production mesh.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs as C
from repro.env import use_compile_cache
from repro.models import model as M


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = C.get_reduced(args.arch) if args.reduced else C.get(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params, _ = M.init(cfg, key)

    b, s = args.batch, args.prompt_len
    prompts = jax.random.randint(key, (b, s), 2, cfg.vocab)
    batch = {"tokens": prompts}
    if cfg.mrope:
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, None], (3, b, s))
    enc_out = None
    if cfg.encoder_layers:
        enc = jax.random.normal(key, (b, max(1, s // 4), cfg.d_model))
        batch["enc_embeds"] = enc
        enc_out = M.encode(cfg, params, enc)

    cache_len = s + args.max_new

    @jax.jit
    def prefill(p, bt):
        return M.prefill(cfg, p, bt, cache_len=cache_len)

    @jax.jit
    def step(p, cache, tok, pos):
        return M.serve_step(cfg, p, cache, tok, pos, enc_out=enc_out)

    t0 = time.perf_counter()
    logits, cache = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(args.max_new - 1):
        logits, cache = step(params, cache, tok, jnp.int32(s + i))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    tok.block_until_ready()
    t_decode = time.perf_counter() - t0

    total_new = b * args.max_new
    print(f"prefill: {t_prefill*1e3:.1f} ms for {b}x{s} tokens "
          f"({b*s/t_prefill:.0f} tok/s)")
    print(f"decode : {t_decode*1e3:.1f} ms for {total_new} tokens "
          f"({total_new/max(t_decode,1e-9):.0f} tok/s)")
    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"generated shape: {gen.shape}; sample: {gen[0, :16].tolist()}")


if __name__ == "__main__":
    main()
