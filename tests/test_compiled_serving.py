"""``DecoderLM.prefill`` / ``decode_step`` take their compiled entry points
when called outside any JAX trace and activation sharding context:
the same logits and cache as the implementation run directly, one lowering
per entry point on the first call and none after, the decode step's cache
donated; under an outer ``jax.jit`` or inside an activation sharding
context they trace the implementation inline.  ``generate`` casts the
stored weights once per decoding call (``DecoderLM.serving_weights``) and
serves the same tokens and logits, bit for bit, as the programs given the
stored weights."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.dist.ctx import activation_sharding_ctx
from repro.launch.serve import generate
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
    # a decoding ``generate`` call gives the programs the cast weights
    weights = model.serving_weights(params)
    want_cache, want = model.prefill(weights, batch, max_len=max_len)
    cache, logits = pre(weights, batch)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    tok = jnp.argmax(want[:, -1], axis=-1).astype(jnp.int32)
    got = dec(weights, cache, tok, pos)
    want_step = model.decode_step(weights, want_cache, tok, pos)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want_step), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _stored_weights_generate(model, params, batch, max_new):
    """``generate``'s greedy loop with the stored weights passed to every
    program, each program casting them itself."""
    prefix = batch.get("patch_embeds", batch["tokens"][:, :0]).shape[1]
    T = batch["tokens"].shape[1]
    cache, logits = model.prefill(params, batch, max_len=prefix + T + max_new)
    seen = [logits[:, -1]]
    out = [jnp.argmax(seen[-1], axis=-1).astype(jnp.int32)]
    for i in range(1, max_new):
        logits, cache = model.decode_step(params, cache, out[-1],
                                          jnp.int32(prefix + T + i - 1))
        seen.append(logits)
        out.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
    return jnp.stack(out, axis=1), jnp.stack(seen, axis=1)


#: tied and untied head; GQA with QKV bias; a router, shared experts and a
#: leading dense layer group
SERVED = [("olmo-1b", True), ("olmo-1b", False), ("qwen2-7b", False),
          ("deepseek-v2-lite", False)]


@pytest.mark.parametrize("arch,tied", SERVED)
def test_generate_on_cast_weights_serves_the_stored_weights_answer(arch, tied):
    cfg = get_smoke_config(arch).scaled(tie_embeddings=tied)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert ("lm_head" in params) != tied
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                          cfg.vocab_size)}
    want = _stored_weights_generate(model, params, batch, 5)
    got = generate(model, params, batch, 5)
    for g, w in zip(got, want, strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _spied_generate(model, params, batch, max_new):
    """``generate``'s spans, and the weights each program call was given."""
    given = []
    for name in ("prefill", "decode_step"):
        real = getattr(model, name)

        def spy(p, *a, _real=real, **kw):
            given.append(p)
            return _real(p, *a, **kw)
        setattr(model, name, spy)
    t0 = time.perf_counter()
    jax.block_until_ready(generate(model, params, batch, max_new))
    return spans.records(t0, time.perf_counter()), given


def test_one_token_call_passes_the_stored_weights():
    model, params, batch, _, _ = _setup("olmo-1b")
    recs, given = _spied_generate(model, params, batch, 1)
    assert len(given) == 1
    assert all(a is b for a, b in zip(jax.tree.leaves(given[0]),
                                      jax.tree.leaves(params), strict=True))
    assert not [r for r in recs if r.name == "generate.cast_weights"]


def test_decoding_call_casts_once():
    model, params, batch, _, _ = _setup("olmo-1b")
    recs, given = _spied_generate(model, params, batch, NEW)
    (cast,) = [r for r in recs if r.name == "generate.cast_weights"]
    (root,) = [r for r in recs if r.name == "generate"]
    assert cast.parent == root.id
    assert len(given) == NEW and all(g is given[0] for g in given)
    weights = given[0]
    assert weights["embed"].dtype == model.dtype
    assert weights["norm_f"] is params["norm_f"]
    nbytes = lambda tree: sum(a.nbytes for a in jax.tree.leaves(tree))  # noqa: E731
    assert cast.attrs == {"bytes_in": nbytes(params), "bytes_out": nbytes(weights)}
    assert nbytes(weights) < nbytes(params)


@pytest.mark.parametrize("arch", ["olmo-1b", "deepseek-v2-lite"])
def test_serving_weights_casts_what_the_programs_cast(arch):
    model = build_model(get_smoke_config(arch))
    params = model.init(jax.random.PRNGKey(0))
    assert model.pdtype == jnp.float32 and model.dtype == jnp.bfloat16
    # a leaf of neither dtype, which the programs' ``_cast`` leaves alone
    attn = params["layers"]["attn"]
    odd = min(attn)
    attn[odd] = attn[odd].astype(jnp.float16)
    weights = model.serving_weights(params)
    assert weights["norm_f"] is params["norm_f"]
    assert weights["layers"]["attn"][odd].dtype == jnp.float16
    np.testing.assert_array_equal(weights["layers"]["attn"][odd], attn[odd])
    for path, a in jax.tree_util.tree_leaves_with_path(weights):
        stored = params
        for k in path:
            stored = stored[k.key]
        if path[0].key != "norm_f" and stored.dtype == model.pdtype:
            assert a.dtype == model.dtype, path
            np.testing.assert_array_equal(a, stored.astype(model.dtype))
    again = model.serving_weights(weights)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(weights), strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_serving_weights_is_the_identity_when_stored_in_the_compute_dtype():
    model = build_model(get_smoke_config("olmo-1b").scaled(param_dtype="bfloat16"))
    params = model.init(jax.random.PRNGKey(0))
    assert model.serving_weights(params) is params
