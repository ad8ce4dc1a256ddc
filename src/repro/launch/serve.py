"""Batched serving launcher: prefill + decode loop over a request queue.

CPU-runnable with reduced configs; the same step functions lower for the
production mesh in dryrun.py (prefill_32k / decode_32k cells).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
      --batch 4 --prompt-len 16 --gen 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from repro.configs import get_config
    from repro.models.registry import get_api
    from repro.train import steps as tsteps
    from repro.launch import specs

    cfg = get_config(args.arch, smoke=args.smoke)
    api = get_api(cfg)
    params = api.init(jax.random.PRNGKey(0), cfg, tp=1)
    prefill = jax.jit(tsteps.make_prefill_step(cfg, api, groups=1))
    decode = jax.jit(tsteps.make_decode_step(cfg, api, groups=1))

    # Synthetic request batch.
    pb = specs.prefill_inputs(cfg, args.prompt_len, args.batch,
                              concrete=True, key=jax.random.PRNGKey(1))
    if cfg.family == "vlm":
        pb = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
            cfg.vocab_size, jnp.int32)}
    cache = api.init_cache(cfg, args.batch, args.max_seq, jnp.float32)

    t0 = time.time()
    logits, cache = prefill(params, pb, cache)
    logits = jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    generated = [np.asarray(tokens)]
    t0 = time.time()
    for _ in range(args.gen):
        tokens, logits, cache = decode(params, tokens, cache)
        generated.append(np.asarray(tokens))
    jax.block_until_ready(tokens)
    t_decode = time.time() - t0
    gen = np.stack(generated, axis=1)
    print(f"arch={cfg.name} batch={args.batch} "
          f"prefill {args.prompt_len} tok in {t_prefill*1e3:.1f} ms; "
          f"{args.gen} decode steps in {t_decode*1e3:.1f} ms "
          f"({t_decode/args.gen*1e3:.2f} ms/step incl. dispatch)")
    print("generated token ids (first request):", gen[0].tolist())
    assert np.isfinite(np.asarray(logits)).all()


if __name__ == "__main__":
    main()
