"""Attention: GQA, causal / sliding-window masks, rotary, KV-cache decode.

Shapes follow (B, T, H, hd).  GQA repeats KV heads by gather-free reshape;
sliding-window attention masks beyond the window (Mixtral).  Decode attends a
single query token against the cache, stored (S, KV, B, hd) — for SWA the
cache is a rolling buffer of ``window`` positions, which is what makes
500k-token contexts O(window).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..dist.ctx import constrain
from .config import ModelConfig
from .layers import apply_rotary, init_dense, rotary

NEG_INF = -1e30


def init_attn_params(rng, cfg: ModelConfig, dtype) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(rng, 4)
    p = {
        "wq": init_dense(ks[0], D, H * hd, dtype),
        "wk": init_dense(ks[1], D, KV * hd, dtype),
        "wv": init_dense(ks[2], D, KV * hd, dtype),
        "wo": init_dense(ks[3], H * hd, D, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    return p


def _project_qkv(p, x, cfg: ModelConfig):
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.dot(x, p["wq"])
    k = jnp.dot(x, p["wk"])
    v = jnp.dot(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (constrain(q.reshape(B, T, H, hd), "heads"),
            constrain(k.reshape(B, T, KV, hd), "heads"),
            constrain(v.reshape(B, T, KV, hd), "heads"))


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, H, hd) by repeating each KV head."""
    B, S, KV, hd = k.shape
    rep = n_heads // KV
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, rep, hd)) \
              .reshape(B, S, n_heads, hd)


#: query-chunk size above which attention runs chunked (memory O(T*chunk))
ATTN_CHUNK = 2048


def _attend(q, k, v, positions, cfg: ModelConfig, causal: bool,
            scale: float | None = None, chunk: int = ATTN_CHUNK) -> jax.Array:
    """Softmax attention on projected/rotated q, k, v (B, T|S, H, hd); v
    may have its own head dim.  Scores are scaled by ``scale``, or divided
    by sqrt(hd).  Sequences longer than ``chunk`` are processed in query
    chunks (lax.scan): exact softmax per row, activation memory
    O(T * chunk) instead of O(T^2)."""
    B, T, H, hd = q.shape
    S = k.shape[1]
    dv = v.shape[-1]

    def block(q_blk, pos_blk):
        scores = constrain(jnp.einsum("bthd,bshd->bhts", q_blk, k), "scores")
        scores = scores / (hd ** 0.5) if scale is None else scores * scale
        if causal:
            i = pos_blk[:, None]
            j = positions[None, :S] if positions.shape[0] >= S \
                else jnp.arange(S)[None, :]
            mask = j <= i
            if cfg.sliding_window:
                mask &= j > i - cfg.sliding_window
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1
                           ).astype(q_blk.dtype)
        return jnp.einsum("bhts,bshd->bthd", w, v)

    if T <= chunk or T % chunk:
        return block(q, positions)

    nc = T // chunk
    qc = jnp.moveaxis(q.reshape(B, nc, chunk, H, hd), 1, 0)
    pc = positions.reshape(nc, chunk)

    def body(_, xs):
        qb, pb = xs
        return None, block(qb, pb)

    _, outs = jax.lax.scan(body, None, (qc, pc))      # (nc, B, c, H, dv)
    return jnp.moveaxis(outs, 0, 1).reshape(B, T, H, dv)


def attention(p: dict, x: jax.Array, cfg: ModelConfig,
              causal: bool = True, positions: jax.Array | None = None) -> jax.Array:
    """Full self-attention over (B, T, D)."""
    B, T, D = x.shape
    H, hd = cfg.n_heads, cfg.hd
    q, k, v = _project_qkv(p, x, cfg)
    if positions is None:
        positions = jnp.arange(T)
    cos, sin = rotary(positions, hd, cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    out = _attend(q, k, v, positions, cfg, causal)
    out = constrain(out, "heads").reshape(B, T, H * hd)
    return constrain(jnp.dot(out, p["wo"]), "residual")


# --------------------------------------------------------------------------- #
# KV-cache serving
# --------------------------------------------------------------------------- #
#
# A layer's cache holds K and V as (S, KV, B, hd): position, KV head, batch,
# head dim, stacked (L, S, KV, B, hd) over a layer group.  This is the order
# the compiled decode attention reads without a relayout (the batch is the
# tiled second-minor dim), so a decode step that carries the stacked cache
# writes one row per layer and copies nothing else.


def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype) -> dict:
    """Cache for one attention layer, (S, KV, B, hd).  SWA archs keep a
    rolling buffer of ``sliding_window`` slots; full attention keeps all
    ``seq_len``."""
    S = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    KV, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((S, KV, batch, hd), dtype),
        "v": jnp.zeros((S, KV, batch, hd), dtype),
    }


def cache_order(k: jax.Array) -> jax.Array:
    """(B, T, KV, hd) -> the cache order (T, KV, B, hd)."""
    return jnp.transpose(k, (1, 2, 0, 3))


def prefill_attention(p, x, cfg: ModelConfig, max_len: int = 0):
    """Run attention AND return the layer cache (S, KV, B, hd), sized for
    subsequent decode up to ``max_len`` positions (rolling buffer for SWA).
    QKV is projected once and shared between the attention output and the
    cache."""
    B, T, D = x.shape
    H = cfg.n_heads
    q, k, v = _project_qkv(p, x, cfg)
    pos = jnp.arange(T)
    cos, sin = rotary(pos, cfg.hd, cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    out = _attend(q, _expand_kv(k, H), _expand_kv(v, H), pos, cfg,
                  causal=True)
    out = constrain(out, "heads").reshape(B, T, H * cfg.hd)
    out = constrain(jnp.dot(out, p["wo"]), "residual")
    max_len = max(max_len, T)
    with jax.named_scope("kv_update"):
        k, v = cache_order(k), cache_order(v)
        S = min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len
        if T > S:
            # SWA rolling buffer of the last S positions: p lives at slot p % S
            k, v = (jnp.roll(a[-S:], T % S, axis=0) for a in (k, v))
        else:
            pad = ((0, S - T), (0, 0), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return out, {"k": k, "v": v}


def cache_slot(pos: jax.Array, S: int, cfg: ModelConfig) -> jax.Array:
    """The cache slot of absolute position ``pos`` in an S-slot cache."""
    return pos % S if cfg.sliding_window else pos


def decode_qkv(p: dict, x: jax.Array, pos: jax.Array, cfg: ModelConfig):
    """Project and rope one token x (B, 1, D) at position ``pos``: the query
    (B, H, hd) and the cache rows of its key and value (1, KV, B, hd)."""
    q, k, v = _project_qkv(p, x, cfg)
    cos, sin = rotary(pos[None], cfg.hd, cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    return q[:, 0], cache_order(k), cache_order(v)


def decode_attend(p: dict, q: jax.Array, k: jax.Array, v: jax.Array,
                  valid: jax.Array | None = None) -> jax.Array:
    """The query (B, H, hd) against one layer's keys and values
    (S, KV, B, hd), each KV head serving its group of query heads, over
    the slots where ``valid`` (S,) holds (all when None); projected by
    ``wo`` to (B, 1, D)."""
    B, H, hd = q.shape
    KV = k.shape[1]
    qg = q.reshape(B, KV, H // KV, hd)
    scores = jnp.einsum("bkgd,skbd->bkgs", qg, k) / (hd ** 0.5)
    if valid is not None:
        scores = jnp.where(valid, scores, NEG_INF)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,skbd->bkgd", w, v).reshape(B, H * hd)
    return jnp.dot(out, p["wo"])[:, None]


def decode_valid(pos: jax.Array, S: int, cfg: ModelConfig) -> jax.Array:
    """(S,) mask of the cache slots that a query at ``pos`` attends to."""
    span = jnp.arange(S)
    if cfg.sliding_window:
        age = (pos % S - span) % S          # rolling-buffer age of each slot
        return (age < cfg.sliding_window) & (age <= pos)
    return span <= pos


def decode_attention(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
                     cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """One-token decode: x (B, 1, D), one layer's cache K/V (S, KV, B, hd),
    pos scalar (current absolute position).  Returns (out (B, 1, D), new
    cache)."""
    q, k, v = decode_qkv(p, x, pos, cfg)
    S = cache["k"].shape[0]
    slot = cache_slot(pos, S, cfg)
    with jax.named_scope("kv_update"):
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (slot, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (slot, 0, 0, 0))
    out = decode_attend(p, q, ck, cv, decode_valid(pos, S, cfg))
    return out, {"k": ck, "v": cv}


def decode_attention_stacked(p: dict, x: jax.Array, cache: dict,
                             layer: jax.Array, pos: jax.Array,
                             cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """``decode_attention`` for layer ``layer`` of a stacked cache
    (L, S, KV, B, hd): writes the token's row into the stack in place and
    attends over that layer read from it.  Returns (out, the stack)."""
    q, k, v = decode_qkv(p, x, pos, cfg)
    S = cache["k"].shape[1]
    at = (layer, cache_slot(pos, S, cfg), 0, 0, 0)
    with jax.named_scope("kv_update"):
        ck = jax.lax.dynamic_update_slice(cache["k"], k[None], at)
        cv = jax.lax.dynamic_update_slice(cache["v"], v[None], at)
    kl = jax.lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
    vl = jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
    out = decode_attend(p, q, kl, vl, decode_valid(pos, S, cfg))
    return out, {"k": ck, "v": cv}


def cross_attention(p: dict, x: jax.Array, kv_src: jax.Array,
                    cfg: ModelConfig) -> jax.Array:
    """Encoder-decoder cross attention (whisper): queries from x, keys and
    values from the encoder output (no mask, no rotary)."""
    B, T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    S = kv_src.shape[1]
    q = jnp.dot(x, p["wq"]).reshape(B, T, H, hd)
    k = jnp.dot(kv_src, p["wk"]).reshape(B, S, KV, hd)
    v = jnp.dot(kv_src, p["wv"]).reshape(B, S, KV, hd)
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / (hd ** 0.5)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = jnp.einsum("bhts,bshd->bthd", w, v).reshape(B, T, H * hd)
    return jnp.dot(out, p["wo"])
