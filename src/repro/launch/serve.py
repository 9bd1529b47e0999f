"""Batched serving driver: continuous-batching-style loop over prefill +
decode steps with a KV/recurrent cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --batch 4 --prompt-len 16 --gen 24

It prints one JSON record; its ``spans`` summarize the ``generate`` call by
span name (``repro.runtime.spans.summarize``): count, host seconds, and
JAX's traces, lowerings and compiles.
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
from ..runtime.spans import records, span, summarize
from .compile_cache import enable_compile_cache


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def generate(model, params, batch, max_new: int, greedy: bool = True,
             rng=None):
    """Prefill the prompt, then decode until ``max_new`` tokens exist.

    Returns ``(tokens, logits)``: tokens (B, max_new) int32, and for each
    token the (B, V) logits it was chosen from — ``logits[:, 0]`` from the
    prefill, ``logits[:, i]`` from the i-th decode step.

    A call that decodes (``max_new > 1``) casts the stored weights to the
    compute dtype once, before the prefill, with the model's
    ``serving_weights`` (``DecoderLM``), and passes the cast weights to
    the prefill and to every decode step, which then cast nothing.  A call
    of one token, and a model without ``serving_weights`` (``HybridLM``,
    ``WhisperModel``, ``XLSTMLM``), leave the casts to the programs.

    Records the spans ``generate`` (the root), ``generate.cast_weights``
    (attrs ``bytes_in``, the weights passed in, and ``bytes_out``, the
    weights the steps get; a decoding call of a model with
    ``serving_weights``), ``generate.prefill``, ``generate.decode`` and one
    ``generate.decode_step`` per step (``repro.runtime.spans``).  Nothing
    here waits for the device, so once the model's programs are compiled
    the spans time their dispatch."""
    cfg = model.cfg
    tokens = batch["tokens"]
    B, T = tokens.shape
    prefix = cfg.frontend_tokens if cfg.family == "vlm" else 0
    max_len = prefix + T + max_new

    def pick(logits):
        nonlocal rng
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        rng, k = jax.random.split(rng)
        return jax.random.categorical(k, logits).astype(jnp.int32)

    with span("generate", batch=B, prompt_len=T, max_new=max_new):
        if max_new > 1 and hasattr(model, "serving_weights"):
            with span("generate.cast_weights", bytes_in=_nbytes(params)) as rec:
                params = model.serving_weights(params)
                rec.attrs["bytes_out"] = _nbytes(params)
        with span("generate.prefill"):
            cache, logits = model.prefill(params, batch, max_len=max_len)
        seen = [logits[:, -1]]
        out = [pick(seen[-1])]
        with span("generate.decode", steps=max_new - 1):
            for i in range(1, max_new):
                pos = jnp.int32(prefix + T + i - 1)
                with span("generate.decode_step", step=i):
                    logits, cache = model.decode_step(params, cache, out[-1],
                                                      pos)
                seen.append(logits)
                out.append(pick(logits))
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
    args = ap.parse_args(argv)
    enable_compile_cache()

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
    record = {
        "arch": cfg.name, "batch": args.batch,
        "prompt_len": args.prompt_len, "generated": args.gen,
        "greedy": not args.sample, "tokens": args.batch * args.gen,
        "spans": summarize(records(t0, time.perf_counter())),
        "sample": np.asarray(toks[0, :8]).tolist(),
    }
    print(json.dumps(record))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema": 1, "rows": [record]}, f, indent=2)
    return toks


if __name__ == "__main__":
    main()
