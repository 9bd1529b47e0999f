"""``DecoderLM.prefill`` / ``decode_step`` take their compiled entry points
when called outside any JAX trace and activation sharding context:
the same logits and cache as the implementation run directly, one lowering
per entry point on the first call and none after, the decode step's cache
donated; under an outer ``jax.jit`` or inside an activation sharding
context they trace the implementation inline."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.dist.ctx import activation_sharding_ctx
from repro.models import build_model
from repro.runtime import spans

#: dense MHA; GQA with QKV bias; a VLM with a patch prefix
ARCHS = ["olmo-1b", "qwen2-7b", "llava-next-34b"]
B, T, NEW = 2, 8, 3
#: the tolerance of ``test_decode_matches_teacher_forcing`` for attention
TOL = 1e-3


def _setup(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    k_tok, k_img = jax.random.split(jax.random.PRNGKey(1))
    batch = {"tokens": jax.random.randint(k_tok, (B, T), 0, cfg.vocab_size)}
    prefix = 0
    if cfg.family == "vlm":
        prefix = cfg.frontend_tokens
        batch["patch_embeds"] = jax.random.normal(
            k_img, (B, prefix, cfg.d_model)).astype(cfg.activation_dtype)
    return model, params, batch, prefix + T + NEW, jnp.int32(prefix + T)


def _close(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=TOL, rtol=TOL)


def _lowerings(fn):
    with spans.span("probe") as probe:
        jax.block_until_ready(fn())
    return probe.counters.get("lowerings", 0)


@pytest.mark.parametrize("arch", ARCHS)
def test_compiled_serving_matches_direct(arch):
    model, params, batch, max_len, pos = _setup(arch)
    want_cache, want = model._prefill(params, batch, max_len=max_len)
    cache, logits = model.prefill(params, batch, max_len=max_len)
    _close((cache, logits), (want_cache, want))
    tok = jnp.argmax(want[:, -1], axis=-1).astype(jnp.int32)
    want_step = model._decode_step(params, want_cache, tok, pos)
    step = model.decode_step(params, cache, tok, pos)
    _close(step, want_step)
    # the compiled step took the cache it was given; the direct one did not
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))
    assert not any(a.is_deleted() for a in jax.tree.leaves(want_cache))


@pytest.mark.parametrize("arch", ARCHS)
def test_compiled_serving_lowers_once_per_shape(arch):
    model, params, batch, max_len, pos = _setup(arch)
    tok = batch["tokens"][:, -1]

    def prefill():
        return model.prefill(params, batch, max_len=max_len)

    def step():
        return model.decode_step(params, prefill()[0], tok, pos)

    assert _lowerings(prefill) == 1
    assert _lowerings(prefill) == 0
    assert _lowerings(step) == 1            # the decode step's first call
    assert _lowerings(step) == 0
    # another position is the same shape: one executable serves every step
    nxt = jnp.int32(int(pos) + 1)
    assert _lowerings(lambda: model.decode_step(
        params, prefill()[0], tok, nxt)) == 0


@pytest.mark.parametrize("caller", ["concrete", "outer_jit", "sharding_ctx"])
@pytest.mark.parametrize("arch", ARCHS)
def test_traced_or_placed_calls_trace_inline(arch, caller, monkeypatch):
    model, params, batch, max_len, pos = _setup(arch)
    tok = batch["tokens"][:, -1]
    want_cache, want = model._prefill(params, batch, max_len=max_len)
    want_step = model._decode_step(params, want_cache, tok, pos)
    taken = []
    for name in ("_prefill_jit", "_decode_jit"):
        real = getattr(model, name)

        def spy(*a, _real=real, _name=name, **kw):
            taken.append(_name)
            return _real(*a, **kw)
        monkeypatch.setattr(model, name, spy)

    def serve():
        cache, logits = model.prefill(params, batch, max_len=max_len)
        return (cache, logits), model.decode_step(params, cache, tok, pos)

    if caller == "concrete":
        got = serve()
    elif caller == "outer_jit":
        got = jax.jit(serve)()
    else:
        with activation_sharding_ctx(lambda name, shape: None):
            got = serve()
    if caller == "concrete":
        assert taken == ["_prefill_jit", "_decode_jit"]
        _close(got[1], want_step)           # the prefill's cache was donated
    else:
        assert taken == []
        _close(got, ((want_cache, want), want_step))


@pytest.mark.parametrize("arch", ARCHS + ["deepseek-v2-lite"])
def test_lower_serving_gives_the_dispatched_programs(arch):
    """``lower_serving`` lowers the programs that ``prefill`` and
    ``decode_step`` run: compiled, they give the served cache, logits and
    step bit for bit, under the entry points' module names."""
    model, params, batch, max_len, pos = _setup(arch)
    pre, dec = (p.compile() for p in model.lower_serving(params, batch, max_len))
    assert pre.as_text().startswith("HloModule jit__prefill,")
    assert dec.as_text().startswith("HloModule jit__decode_step,")
    want_cache, want = model.prefill(params, batch, max_len=max_len)
    cache, logits = pre(params, batch)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    tok = jnp.argmax(want[:, -1], axis=-1).astype(jnp.int32)
    got = dec(params, cache, tok, pos)
    want_step = model.decode_step(params, want_cache, tok, pos)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want_step), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
