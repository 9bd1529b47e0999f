"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), no q LoRA.

Per layer, with ``dn``/``dr`` the no-rope/rope query-key dims of a head,
``dv`` its value dim and ``r`` the latent rank:

    q = x Wq                       (H heads of dn + dr: [q_nope, q_pe])
    [c, k_pe] = x Wkva;  c = rmsnorm(c)        (r + dr, k_pe shared by heads)
    [k_nope, v] = c Wkvb           (H heads of dn + dv)
    q_pe, k_pe = rope(q_pe), rope(k_pe)        (YaRN frequencies, pairs
                                                (2i, 2i+1) rotated together)
    out = softmax([q_nope, q_pe] . [k_nope, k_pe] * scale, causal) v Wo

The cache keeps the normalised latent ``c`` and the roped ``k_pe``, one
row of r + dr per token and layer.  Prefill and training expand the
latent to per-head keys and values; a decode step absorbs ``Wkvb`` into
the query and the output instead (``q_nope Wuk`` into latent space,
attention over the latent cache, then ``Wuv``), so it never expands the
cache.  Named scopes: ``mla.latent`` (the latent and its rope),
``mla.absorb`` and ``mla.attend``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .attention import NEG_INF, _attend
from .config import ModelConfig
from .layers import init_dense, rmsnorm

#: bytes of one query chunk's f32 scores (B, H, chunk, S) in prefill
SCORE_BYTES = 2 ** 30


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """The rope dims' inverse frequencies: the base frequencies, and with
    YaRN those divided by the factor, blended by a linear ramp between the
    dims that turn ``beta_fast`` and ``beta_slow`` times over the original
    context."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.yarn_factor <= 1:
        return extra.astype(np.float32)

    def corr_dim(rotations):
        return (dim * math.log(cfg.yarn_original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inter = extra / cfg.yarn_factor
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(cfg: ModelConfig) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        s *= _yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return s


def _rope(x: jax.Array, pos: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Rotate the pairs (2i, 2i+1) of x (B, T, [H,] dr) by position."""
    ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)     # (T, dr/2)
    m = 1.0
    if cfg.yarn_factor:
        m = (_yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / _yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    pairs = x.reshape(x.shape[:-1] + (-1, 2)).astype(jnp.float32)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def init_attn_params(rng, cfg: ModelConfig, dtype) -> dict:
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(rng, 4)
    return {"wq": init_dense(ks[0], D, H * (dn + dr), dtype),
            "wkv_a": init_dense(ks[1], D, r + dr, dtype),
            "norm_kv": jnp.ones((r,), jnp.float32),
            "wkv_b": init_dense(ks[2], r, H * (dn + dv), dtype),
            "wo": init_dense(ks[3], H * dv, D, dtype)}


def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype) -> dict:
    return {"latent": jnp.zeros((batch, seq_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, seq_len, cfg.qk_rope_head_dim), dtype)}


def _project(p, x, pos, cfg: ModelConfig):
    """(q_nope, roped q_pe) per head, the normalised latent and the roped
    shared key, for x (B, T, D) at positions ``pos`` (T,)."""
    B, T, _ = x.shape
    H, dn, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.dot(x, p["wq"]).reshape(B, T, H, -1)
    with jax.named_scope("mla.latent"):
        ckv = jnp.dot(x, p["wkv_a"])
        c = rmsnorm(ckv[..., :r], p["norm_kv"])
        k_pe = _rope(ckv[..., r:], pos, cfg)
    return q[..., :dn], _rope(q[..., dn:], pos, cfg), c, k_pe


def _query_chunk(B: int, H: int, T: int) -> int:
    """The largest power-of-two query chunk that divides T and keeps the
    chunk's f32 scores within ``SCORE_BYTES``."""
    c = 1 << max(T.bit_length() - 1, 0)
    while c > 1 and (B * H * c * T * 4 > SCORE_BYTES or T % c):
        c //= 2
    return c


def _forward(p, x, cfg: ModelConfig):
    """Per-head attention over x (B, T, D): (out, latent, roped key)."""
    B, T, _ = x.shape
    H, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    pos = jnp.arange(T)
    q_nope, q_pe, c, k_pe = _project(p, x, pos, cfg)
    kv = jnp.dot(c, p["wkv_b"]).reshape(B, T, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], (B, T, H, k_pe.shape[-1]))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    with jax.named_scope("mla.attend"):
        out = _attend(q, k, kv[..., dn:], pos, cfg, causal=True,
                      scale=softmax_scale(cfg), chunk=_query_chunk(B, H, T))
    return jnp.dot(out.reshape(B, T, H * dv), p["wo"]), c, k_pe


def attention(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    return _forward(p, x, cfg)[0]


def prefill_attention(p, x, cfg: ModelConfig, max_len: int = 0):
    """Attention over the prompt and the layer's latent cache, sized for
    ``max_len`` positions."""
    T = x.shape[1]
    out, c, k_pe = _forward(p, x, cfg)
    pad = ((0, 0), (0, max(max_len, T) - T), (0, 0))
    with jax.named_scope("kv_update"):
        return out, {"latent": jnp.pad(c, pad), "k_rope": jnp.pad(k_pe, pad)}


def decode_attention(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
                     cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """One token x (B, 1, D) at position ``pos`` against the latent cache,
    in the absorbed form."""
    B, _, D = x.shape
    H, dn, dv, r = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q_nope, q_pe, c, k_pe = _project(p, x, pos[None], cfg)
    with jax.named_scope("kv_update"):
        lat = jax.lax.dynamic_update_slice(cache["latent"], c, (0, pos, 0))
        kr = jax.lax.dynamic_update_slice(cache["k_rope"], k_pe, (0, pos, 0))
    w_b = p["wkv_b"].reshape(r, H, dn + dv)
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_b[..., :dn])
    with jax.named_scope("mla.attend"):
        f32 = jnp.float32
        s = (jnp.einsum("bhr,bsr->bhs", q_lat, lat, preferred_element_type=f32)
             + jnp.einsum("bhp,bsp->bhs", q_pe[:, 0], kr, preferred_element_type=f32))
        s = jnp.where(jnp.arange(lat.shape[1]) <= pos, s * softmax_scale(cfg), NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhs,bsr->bhr", w, lat)
    with jax.named_scope("mla.absorb"):
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w_b[..., dn:])
    out = jnp.dot(o.reshape(B, H * dv), p["wo"]).reshape(B, 1, D)
    return out, {"latent": lat, "k_rope": kr}
