"""Whisper-style encoder-decoder backbone.

The audio conv frontend is a STUB per the assignment: ``input_specs`` feeds
precomputed frame embeddings (B, T_audio, d_model) directly to the encoder.
The decoder is a causal transformer with cross-attention; decode caches both
the self-attention KV and the per-layer cross KV projections, each in the
attention cache order (L, S, KV, B, hd)."""
from __future__ import annotations


import jax
import jax.numpy as jnp

from .attention import (attention, cache_order, cross_attention,
                        decode_attend, decode_attention, init_attn_params,
                        init_kv_cache, prefill_attention)
from .config import ModelConfig
from .layers import cross_entropy_loss, init_dense, norm_fn
from .transformer import ffn, init_ffn_params


class WhisperModel:
    def __init__(self, cfg: ModelConfig):
        assert cfg.encoder_layers > 0
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)
        self.pdtype = jnp.dtype(cfg.param_dtype)

    def init(self, rng) -> dict:
        cfg = self.cfg
        ks = jax.random.split(rng, 6)

        def enc_layer(k):
            k1, k2 = jax.random.split(k)
            return {"attn": init_attn_params(k1, cfg, self.pdtype),
                    "ffn": init_ffn_params(k2, cfg, self.pdtype),
                    "norm1": jnp.ones((cfg.d_model,), jnp.float32),
                    "norm2": jnp.ones((cfg.d_model,), jnp.float32)}

        def dec_layer(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {"self": init_attn_params(k1, cfg, self.pdtype),
                    "cross": init_attn_params(k2, cfg, self.pdtype),
                    "ffn": init_ffn_params(k3, cfg, self.pdtype),
                    "norm1": jnp.ones((cfg.d_model,), jnp.float32),
                    "norm2": jnp.ones((cfg.d_model,), jnp.float32),
                    "norm3": jnp.ones((cfg.d_model,), jnp.float32)}

        enc = jax.vmap(enc_layer)(jax.random.split(ks[0], cfg.encoder_layers))
        dec = jax.vmap(dec_layer)(jax.random.split(ks[1], cfg.n_layers))
        return {
            "embed": (jax.random.normal(
                ks[2], (cfg.vocab_size, cfg.d_model), jnp.float32)
                * 0.02).astype(self.pdtype),
            "enc": enc,
            "dec": dec,
            "norm_enc": jnp.ones((cfg.d_model,), jnp.float32),
            "norm_f": jnp.ones((cfg.d_model,), jnp.float32),
            "lm_head": init_dense(ks[3], cfg.d_model, cfg.vocab_size,
                                  self.pdtype),
        }

    def _cast(self, tree):
        return jax.tree.map(
            lambda a: a.astype(self.dtype) if a.dtype == self.pdtype else a,
            tree)

    # ---- encoder --------------------------------------------------------------
    def encode(self, params, audio_embeds) -> jax.Array:
        cfg = self.cfg
        nf = norm_fn(cfg.norm)
        x = audio_embeds.astype(self.dtype)

        def body(h, lp):
            lp = self._cast(lp)
            h = h + attention(lp["attn"], nf(h, lp["norm1"]), cfg,
                              causal=False)
            h = h + ffn(lp["ffn"], nf(h, lp["norm2"]), cfg)
            return h, None

        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["enc"])
        return norm_fn("rmsnorm")(x, params["norm_enc"])

    # ---- decoder (teacher forcing) ----------------------------------------------
    def logits(self, params, batch) -> jax.Array:
        cfg = self.cfg
        nf = norm_fn(cfg.norm)
        enc_out = self.encode(params, batch["audio_embeds"])
        x = jnp.take(params["embed"].astype(self.dtype), batch["tokens"],
                     axis=0)

        def body(h, lp):
            lp = self._cast(lp)
            h = h + attention(lp["self"], nf(h, lp["norm1"]), cfg)
            h = h + cross_attention(lp["cross"], nf(h, lp["norm2"]), enc_out,
                                    cfg)
            h = h + ffn(lp["ffn"], nf(h, lp["norm3"]), cfg)
            return h, None

        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["dec"])
        x = norm_fn("rmsnorm")(x, params["norm_f"])
        return jnp.dot(x, params["lm_head"].astype(self.dtype))

    def loss(self, params, batch) -> jax.Array:
        logits = self.logits(params, batch)
        return cross_entropy_loss(logits[:, :-1], batch["tokens"][:, 1:])

    # ---- serving -----------------------------------------------------------------
    def init_cache(self, batch: int, seq_len: int) -> dict:
        cfg = self.cfg

        def stacked(length):
            one = init_kv_cache(cfg, batch, length, self.dtype)
            return jax.tree.map(lambda a: jnp.broadcast_to(
                a[None], (cfg.n_layers,) + a.shape), one)

        return {"kv": stacked(seq_len),
                "cross": stacked(cfg.frontend_tokens or 1500)}

    def prefill(self, params, batch, max_len: int = 0):
        """Encode audio, consume the text prompt, cache self+cross KV."""
        cfg = self.cfg
        nf = norm_fn(cfg.norm)
        enc_out = self.encode(params, batch["audio_embeds"])
        x = jnp.take(params["embed"].astype(self.dtype), batch["tokens"],
                     axis=0)
        B, Ta, D = enc_out.shape
        KV, hd = cfg.n_kv_heads, cfg.hd

        def body(h, lp):
            lp = self._cast(lp)
            a, kv = prefill_attention(lp["self"], nf(h, lp["norm1"]), cfg,
                                      max_len=max_len)
            h = h + a
            ck = jnp.dot(enc_out, lp["cross"]["wk"]).reshape(B, Ta, KV, hd)
            cv = jnp.dot(enc_out, lp["cross"]["wv"]).reshape(B, Ta, KV, hd)
            ck, cv = cache_order(ck), cache_order(cv)
            h = h + cross_attention(lp["cross"], nf(h, lp["norm2"]), enc_out,
                                    cfg)
            h = h + ffn(lp["ffn"], nf(h, lp["norm3"]), cfg)
            return h, (kv, {"k": ck, "v": cv})

        x, (kvs, crosses) = jax.lax.scan(body, x, params["dec"])
        x = norm_fn("rmsnorm")(x, params["norm_f"])
        logits = jnp.dot(x[:, -1:], params["lm_head"].astype(self.dtype))
        return {"kv": kvs, "cross": crosses}, logits

    def decode_step(self, params, cache, tokens, pos):
        cfg = self.cfg
        nf = norm_fn(cfg.norm)
        x = jnp.take(params["embed"].astype(self.dtype), tokens[:, None],
                     axis=0)
        H, hd = cfg.n_heads, cfg.hd

        def body(h, xs):
            lp, kv_c, cross_c = xs
            lp = self._cast(lp)
            a, kv2 = decode_attention(lp["self"], nf(h, lp["norm1"]), kv_c,
                                      pos, cfg)
            h = h + a
            # cross attention against cached enc projections
            B = h.shape[0]
            q = jnp.dot(nf(h, lp["norm2"]),
                        lp["cross"]["wq"]).reshape(B, H, hd)
            h = h + decode_attend(lp["cross"], q, cross_c["k"], cross_c["v"])
            h = h + ffn(lp["ffn"], nf(h, lp["norm3"]), cfg)
            return h, kv2

        x, kv2 = jax.lax.scan(body, x,
                              (params["dec"], cache["kv"], cache["cross"]))
        x = norm_fn("rmsnorm")(x, params["norm_f"])
        logits = jnp.dot(x, params["lm_head"].astype(self.dtype))[:, 0]
        return logits, {"kv": kv2, "cross": cache["cross"]}
