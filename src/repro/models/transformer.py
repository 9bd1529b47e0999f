"""Decoder-only transformer LM (dense / MoE / VLM backbone).

Layers are parameter-stacked and driven by ``jax.lax.scan`` so compile time
and HLO size are O(1) in depth — essential for the 512-device dry-runs.
Remat (``jax.checkpoint``) wraps the scanned body when cfg.remat is set.
Leading dense layers (``first_dense_layers``, DeepSeek-V2's layer 0) are a
second stacked group, scanned before the main stack, with their own cache.
Attention is GQA/MHA (``attention.py``) or, when ``kv_lora_rank`` is set,
multi-head latent attention (``mla.py``).

``DecoderLM.prefill`` and ``decode_step``, called outside any JAX trace and
activation sharding context, run a ``jax.jit`` of their implementation that
each model instance compiles once per input shape; under an outer trace or
inside a sharding context they trace their implementation inline.
``DecoderLM.serving_weights`` casts the stored weights to the compute dtype
ahead of those programs, which then cast nothing and compute the same
numbers: a caller that runs many of them casts once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..dist.ctx import constrain, current_rules
from . import attention as gqa
from . import mla
from .config import ModelConfig
from .layers import cross_entropy_loss, init_dense, norm_fn
from .moe import init_moe_params, moe_ffn


def init_ffn_params(rng, cfg: ModelConfig, dtype) -> dict:
    if cfg.n_experts:
        return init_moe_params(rng, cfg, dtype)
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 3)
    return {"w_gate": init_dense(ks[0], D, F, dtype),
            "w_up": init_dense(ks[1], D, F, dtype),
            "w_down": init_dense(ks[2], F, D, dtype)}


def ffn(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.n_experts:
        return moe_ffn(p, x, cfg)
    g = jax.nn.silu(jnp.dot(x, p["w_gate"]))
    u = jnp.dot(x, p["w_up"])
    h = constrain(g * u, "ffn_hidden")
    return constrain(jnp.dot(h, p["w_down"]), "residual")


def _attn(cfg: ModelConfig):
    """The layer's attention module: GQA/MHA, or multi-head latent."""
    return mla if cfg.kv_lora_rank else gqa


def init_layer_params(rng, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(rng)
    p = {"attn": _attn(cfg).init_attn_params(k1, cfg, dtype),
         "ffn": init_ffn_params(k2, cfg, dtype)}
    if cfg.norm == "rmsnorm":
        p["norm1"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
    return p


def _norms(p, cfg):
    nf = norm_fn(cfg.norm)
    n1 = functools.partial(nf, scale=p.get("norm1"))
    n2 = functools.partial(nf, scale=p.get("norm2"))
    return n1, n2


# Named scopes (``attn``, ``ffn``, ``kv_update``, ``weight_cast``, ``embed``,
# ``final_norm``, ``head``) label the compiled operations of each layer
# part in the program's op metadata and so in a device trace.


def layer_fwd(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    n1, n2 = _norms(p, cfg)
    x = constrain(x, "residual")
    with jax.named_scope("attn"):
        x = x + _attn(cfg).attention(p["attn"], n1(x), cfg)
    with jax.named_scope("ffn"):
        x = x + ffn(p["ffn"], n2(x), cfg)
    return constrain(x, "residual")


def layer_prefill(p: dict, x: jax.Array, cfg: ModelConfig, max_len: int = 0):
    n1, n2 = _norms(p, cfg)
    with jax.named_scope("attn"):
        a, cache = _attn(cfg).prefill_attention(p["attn"], n1(x), cfg,
                                                  max_len=max_len)
        x = x + a
    with jax.named_scope("ffn"):
        x = x + ffn(p["ffn"], n2(x), cfg)
    return x, cache


def layer_decode(p: dict, x: jax.Array, attend, cfg: ModelConfig):
    """One decode layer; ``attend(attn_params, normed_x)`` is the layer's
    cached attention and returns (out, cache)."""
    n1, n2 = _norms(p, cfg)
    with jax.named_scope("attn"):
        a, cache = attend(p["attn"], n1(x))
        x = x + a
    with jax.named_scope("ffn"):
        x = x + ffn(p["ffn"], n2(x), cfg)
    return x, cache


def _dispatch_now() -> bool:
    """True outside any JAX trace (jit, vmap, grad, eval_shape) and any
    activation sharding context: the call runs now on concrete arrays, at
    their own placement, so a compiled entry point, whose cache sees
    neither an outer trace nor the context, may serve it."""
    return current_rules() is None and jax.core.trace_ctx.is_top_level()


class DecoderLM:
    """Families: dense (olmo/qwen*), moe (mixtral/phi3.5-moe/deepseek-v2),
    vlm (llava)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        # (params key, cache key, config, layers) of each stacked group
        self.groups = [("layers", "kv", cfg, cfg.n_layers - cfg.first_dense_layers)]
        if cfg.first_dense_layers:
            self.groups.insert(0, ("dense_layers", "kv_dense",
                                   cfg.scaled(n_experts=0),
                                   cfg.first_dense_layers))
        self.dtype = jnp.dtype(cfg.dtype)
        self.pdtype = jnp.dtype(cfg.param_dtype)
        # serving entry points, compiled once per instance and input shape
        self._prefill_jit = jax.jit(self._prefill, static_argnames="max_len")
        self._decode_jit = jax.jit(self._decode_step, donate_argnames="cache")
        self._cast_jit = jax.jit(self._cast)

    # ---- parameters -------------------------------------------------------
    def init(self, rng) -> dict:
        cfg = self.cfg
        ks = jax.random.split(rng, 4)
        p = {
            "embed": (jax.random.normal(
                ks[1], (cfg.vocab_size, cfg.d_model), jnp.float32)
                * 0.02).astype(self.pdtype),
            "norm_f": jnp.ones((cfg.d_model,), jnp.float32),
        }
        for (key, _, gcfg, n), k in zip(self.groups[::-1], (ks[0], ks[3])):
            p[key] = jax.vmap(lambda k, gcfg=gcfg: init_layer_params(
                k, gcfg, self.pdtype))(jax.random.split(k, n))
        if not cfg.tie_embeddings:
            p["lm_head"] = init_dense(ks[2], cfg.d_model, cfg.vocab_size,
                                      self.pdtype)
        return p

    def serving_weights(self, params) -> dict:
        """``params`` with the weights the programs cast to the compute dtype
        already cast: each layer group's stored-dtype leaves (``_cast``'s
        rule), ``embed`` and ``lm_head``.  Every other leaf (``norm_f``)
        is passed through.  The programs' own casts then compile to nothing
        and their arithmetic is unchanged.  The identity when the stored
        dtype is the compute dtype; one compiled program when
        ``_dispatch_now``, else traced inline (``params`` may then be
        ``jax.ShapeDtypeStruct``s, under ``jax.eval_shape``)."""
        if self.pdtype == self.dtype:
            return params
        keys = [key for key, *_ in self.groups] + ["embed", "lm_head"]
        stored = {k: params[k] for k in keys if k in params}
        cast = self._cast_jit if _dispatch_now() else self._cast
        return {**params, **cast(stored)}

    # ---- embedding / head ----------------------------------------------------
    def _cast(self, tree):
        """The stored weights in ``tree`` as compute-dtype copies."""
        with jax.named_scope("weight_cast"):
            return jax.tree.map(lambda a: a.astype(self.dtype)
                                if a.dtype == self.pdtype else a, tree)

    def _embed(self, params, tokens) -> jax.Array:
        with jax.named_scope("weight_cast"):
            table = params["embed"].astype(self.dtype)
        with jax.named_scope("embed"):
            return jnp.take(table, tokens, axis=0)

    def _embed_tokens(self, params, batch) -> jax.Array:
        x = constrain(self._embed(params, batch["tokens"]), "residual")
        if self.cfg.family == "vlm" and "patch_embeds" in batch:
            # anyres frontend stub: precomputed patch embeddings are prefixed
            x = jnp.concatenate(
                [batch["patch_embeds"].astype(self.dtype), x], axis=1)
        return x

    def _final_norm(self, params, x) -> jax.Array:
        with jax.named_scope("final_norm"):
            return norm_fn("rmsnorm")(x, params["norm_f"])

    def _head(self, params, x) -> jax.Array:
        w = params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]
        with jax.named_scope("weight_cast"):
            w = w.astype(self.dtype)
        with jax.named_scope("head"):
            return constrain(jnp.dot(x, w), "logits")

    # ---- scanned layer stack ---------------------------------------------------
    def _run_layers(self, params, x) -> jax.Array:
        for key, _, cfg, _ in self.groups:
            def body(h, layer_p, cfg=cfg):
                return layer_fwd(self._cast(layer_p), h, cfg), None

            if cfg.remat:
                body = jax.checkpoint(body)
            x, _ = jax.lax.scan(body, x, params[key])
        return x

    def logits(self, params, batch) -> jax.Array:
        x = self._embed_tokens(params, batch)
        x = self._run_layers(params, x)
        return self._head(params, self._final_norm(params, x))

    def loss(self, params, batch) -> jax.Array:
        logits = self.logits(params, batch)
        T = batch["tokens"].shape[1]
        logits_txt = logits[:, -T:]                      # vlm: text positions
        return cross_entropy_loss(logits_txt[:, :-1], batch["tokens"][:, 1:])

    # ---- serving ----------------------------------------------------------------
    def init_cache(self, batch: int, seq_len: int) -> dict:
        cache = {}
        for _, key, cfg, n in self.groups:
            one = _attn(cfg).init_kv_cache(cfg, batch, seq_len, self.dtype)
            cache[key] = jax.tree.map(
                lambda a, n=n: jnp.broadcast_to(a[None], (n,) + a.shape), one)
        return cache

    def prefill(self, params, batch, max_len: int = 0):
        """(cache sized for ``max_len`` positions, last position's logits);
        compiled per shape when ``_dispatch_now``, else traced inline."""
        if _dispatch_now():
            return self._prefill_jit(params, batch, max_len=max_len)
        return self._prefill(params, batch, max_len=max_len)

    def decode_step(self, params, cache, tokens, pos):
        """tokens (B,) int32; pos scalar int32 absolute position.  Compiled
        once per shape when ``_dispatch_now``, with ``cache`` donated (its
        arrays are deleted by the call); else traced inline."""
        if _dispatch_now():
            return self._decode_jit(params, cache, tokens, pos)
        return self._decode_step(params, cache, tokens, pos)

    def lower_serving(self, params, batch, max_len: int):
        """The programs that ``prefill`` and ``decode_step`` dispatch to in a
        decoding call (``launch.serve.generate``, which passes them
        ``serving_weights(params)``) for ``batch``'s shape and a cache of
        ``max_len`` positions, lowered (``jax.stages.Lowered``: compile one
        to read its HLO or its memory); arguments may be
        ``jax.ShapeDtypeStruct``s."""
        params = jax.eval_shape(self.serving_weights, params)
        prefill = self._prefill_jit.lower(params, batch, max_len=max_len)
        cache = jax.eval_shape(
            lambda p, b: self._prefill(p, b, max_len=max_len)[0], params, batch)
        B = batch["tokens"].shape[0]
        decode = self._decode_jit.lower(params, cache,
                                        jax.ShapeDtypeStruct((B,), jnp.int32),
                                        jax.ShapeDtypeStruct((), jnp.int32))
        return prefill, decode

    def _prefill(self, params, batch, max_len: int = 0):
        x = self._embed_tokens(params, batch)
        caches = {}
        for key, ckey, cfg, _ in self.groups:
            def body(h, layer_p, cfg=cfg):
                h2, cache = layer_prefill(self._cast(layer_p), h, cfg,
                                          max_len=max_len)
                return h2, cache

            if cfg.remat:
                body = jax.checkpoint(body)
            x, caches[ckey] = jax.lax.scan(body, x, params[key])
        x = self._final_norm(params, x)
        return caches, self._head(params, x[:, -1:])

    def _decode_step(self, params, cache, tokens, pos):
        """A GQA/MHA group carries its stacked cache through the layer scan
        and each layer writes its token's row in place; a latent (MLA)
        group maps each layer's cache through the scan, since its two
        contractions over the latent read it in conflicting orders."""
        x = self._embed(params, tokens[:, None])
        new_caches = {}
        for key, ckey, cfg, n in self.groups:
            if _attn(cfg) is gqa:
                def body(carry, xs, cfg=cfg):
                    h, kv = carry
                    layer_p, i = xs
                    return layer_decode(
                        self._cast(layer_p), h,
                        lambda p, y: gqa.decode_attention_stacked(
                            p, y, kv, i, pos, cfg), cfg), None

                (x, new_caches[ckey]), _ = jax.lax.scan(
                    body, (x, cache[ckey]), (params[key], jnp.arange(n)))
            else:
                def body(h, xs, cfg=cfg):
                    layer_p, layer_cache = xs
                    return layer_decode(
                        self._cast(layer_p), h,
                        lambda p, y: mla.decode_attention(
                            p, y, layer_cache, pos, cfg), cfg)

                x, new_caches[ckey] = jax.lax.scan(
                    body, x, (params[key], cache[ckey]))
        x = self._final_norm(params, x)
        return self._head(params, x)[:, 0], new_caches
