"""Batched LM serving driver: prefill (chunked) + cached greedy decode.

This is the runtime counterpart of the decode_32k / long_500k dry-run
shapes. On real hardware you'd pass --data-par/--model-par to shard the
cache; on CPU it runs reduced configs.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api, lm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=0, help="default prompt+gen")
    args = ap.parse_args()
    enable_compile_cache()

    mod = get_arch(args.arch)
    cfg = mod.smoke_config() if args.smoke else mod.config()
    cache_len = args.cache_len or (args.prompt_len + args.gen)
    print(f"{cfg.name}: {cfg.n_layers}L d={cfg.d_model} ({cfg.arch_type}); "
          f"batch={args.batch} cache={cache_len}")

    key = jax.random.key(0)
    params = lm.init_params(cfg, key)
    serve = jax.jit(api.make_serve_step(cfg))
    cache = api.init_cache(cfg, args.batch, cache_len)

    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab)

    # prefill by stepping the decode cache through the prompt (token-by-token
    # cache population; a fused prefill that bulk-writes the cache is the
    # enumerated §Perf follow-up for serving)
    t0 = time.perf_counter()
    logits = None
    for t in range(args.prompt_len):
        logits, cache = serve(params, cache, prompt[:, t : t + 1], jnp.asarray(t, jnp.int32))
    t_prefill = time.perf_counter() - t0

    toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [np.asarray(toks[:, 0])]
    t0 = time.perf_counter()
    for t in range(args.prompt_len, args.prompt_len + args.gen - 1):
        logits, cache = serve(params, cache, toks, jnp.asarray(t, jnp.int32))
        toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out.append(np.asarray(toks[:, 0]))
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0

    gen = np.stack(out, axis=1)
    print("generated ids:\n", gen)
    print(f"prefill {t_prefill*1e3:.0f} ms ({args.prompt_len} steps), "
          f"decode {t_decode/max(args.gen-1,1)*1e3:.1f} ms/token")


if __name__ == "__main__":
    main()
