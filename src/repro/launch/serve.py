"""Batched serving driver: continuous-batching-style loop over prefill +
decode steps with a KV/recurrent cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --batch 4 --prompt-len 16 --gen 24
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, get_config, get_smoke_config
from ..models import build_model
from .compile_cache import enable_compile_cache


def generate(model, params, batch, max_new: int, greedy: bool = True,
             rng=None):
    """Prefill the prompt, then decode until ``max_new`` tokens exist.

    Returns ``(tokens, logits)``: tokens (B, max_new) int32, and for each
    token the (B, V) logits it was chosen from — ``logits[:, 0]`` from the
    prefill, ``logits[:, i]`` from the i-th decode step."""
    cfg = model.cfg
    tokens = batch["tokens"]
    B, T = tokens.shape
    prefix = cfg.frontend_tokens if cfg.family == "vlm" else 0
    max_len = prefix + T + max_new
    cache, logits = model.prefill(params, batch, max_len=max_len)
    logits = logits[:, -1]
    out, seen = [], []
    for i in range(max_new):
        if i:
            pos = jnp.int32(prefix + T + i - 1)
            logits, cache = model.decode_step(params, cache, out[-1], pos)
        seen.append(logits)
        if greedy:
            cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            rng, k = jax.random.split(rng)
            cur = jax.random.categorical(k, logits).astype(jnp.int32)
        out.append(cur)
    return jnp.stack(out, axis=1), jnp.stack(seen, axis=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--sample", action="store_true",
                    help="sample from the logits instead of greedy argmax")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the result record as JSON (mirrors "
                         "benchmarks/run.py --json)")
    ap.add_argument("--tuned", action="store_true",
                    help="activate the repro.search tuning cache and the "
                         "repro.compile artifact cache for this process: "
                         "cache-aware ISAM kernels pick up autotuned configs "
                         "and precompiled CompiledKernel artifacts")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="tuning cache path (with --tuned)")
    ap.add_argument("--compile-cache", default=None, metavar="PATH",
                    help="artifact cache path (with --tuned)")
    ap.add_argument("--tuning-model", default=None, metavar="PATH",
                    help="learned cost model store (with --tuned): untuned "
                         "GEMM shapes get a model-predicted BlockSpec")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.tuned:
        from .train import activate_caches
        activate_caches(args.tuning_cache, args.compile_cache, tag="serve",
                        model_path=args.tuning_model)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    # One split per consumer: reusing a PRNG key across init / randint /
    # normal / categorical correlates the streams.
    rng = jax.random.PRNGKey(0)
    rng, k_init, k_tokens, k_audio, k_gen = jax.random.split(rng, 5)
    params = model.init(k_init)

    batch = {"tokens": jax.random.randint(
        k_tokens, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.zeros(
            (args.batch, cfg.frontend_tokens, cfg.d_model),
            cfg.activation_dtype)
    if cfg.family == "audio":
        batch["audio_embeds"] = jax.random.normal(
            k_audio, (args.batch, cfg.frontend_tokens, cfg.d_model)
        ).astype(cfg.activation_dtype)

    t0 = time.perf_counter()
    toks, _ = generate(model, params, batch, args.gen,
                       greedy=not args.sample, rng=k_gen)
    toks.block_until_ready()
    dt = time.perf_counter() - t0
    total = args.batch * args.gen
    record = {
        "arch": cfg.name, "batch": args.batch,
        "prompt_len": args.prompt_len, "generated": args.gen,
        "greedy": not args.sample,
        "tokens": int(total), "wall_s": round(dt, 3),
        "tok_per_s": round(total / dt, 2),
        "sample": np.asarray(toks[0, :8]).tolist(),
    }
    print(json.dumps(record))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema": 1, "rows": [record]}, f, indent=2)
    return toks


if __name__ == "__main__":
    main()
