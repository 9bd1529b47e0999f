"""Served decoding against the same model's full forward pass, in f32: a
prompt's prefill, then decode steps one token at a time through the KV
cache (stored (S, KV, B, hd) per layer), give the logits of the forward
pass over the whole sequence at every position.

``DecoderLM`` carries each stacked cache through its layer scan and writes
one row per layer in place; jamba's and whisper's per-layer loops share
the cache's order through ``attention.decode_attention``.  Mixtral's
sliding window of 8 is shorter than the prompt and the decode, so its
rolling buffer wraps in the prefill and again in the decode steps."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model

B, PROMPT, STEPS = 2, 10, 11
#: largest |decoded logit - forward logit| over the largest |forward logit|
REL = 1e-5

CASES = {
    "mha": "olmo-1b",
    "gqa": "qwen2-7b",
    "sliding-window": "mixtral-8x7b",
    "vlm": "llava-next-34b",
    "hybrid": "jamba-1.5-large-398b",
    "encoder-decoder": "whisper-medium",
}


def _rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


@pytest.mark.parametrize("arch", CASES.values(), ids=CASES.keys())
def test_decode_matches_forward(arch):
    cfg = get_smoke_config(arch).scaled(dtype="float32",
                                        param_dtype="float32")
    if arch == "mixtral-8x7b":
        assert cfg.sliding_window == 8 < PROMPT
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    k_tok, k_in = jax.random.split(jax.random.PRNGKey(1))
    T = PROMPT + STEPS
    batch = {"tokens": jax.random.randint(k_tok, (B, T), 0, cfg.vocab_size)}
    prefix = 0
    if cfg.family == "vlm":
        prefix = cfg.frontend_tokens
        batch["patch_embeds"] = jax.random.normal(
            k_in, (B, prefix, cfg.d_model))
    if cfg.family == "audio":
        batch["audio_embeds"] = jax.random.normal(
            k_in, (B, cfg.frontend_tokens, cfg.d_model))
    full = model.logits(params, batch)[:, prefix:]

    prompt = dict(batch, tokens=batch["tokens"][:, :PROMPT])
    cache, logits = model.prefill(params, prompt, max_len=prefix + T)
    assert _rel(logits[:, -1], full[:, PROMPT - 1]) <= REL
    for t in range(PROMPT, T):
        logits, cache = model.decode_step(params, cache, batch["tokens"][:, t],
                                          jnp.int32(prefix + t))
        assert _rel(logits, full[:, t]) <= REL, t
