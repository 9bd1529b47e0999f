"""Plain float32 reference of DeepSeek-V2 (arXiv:2405.04434; the published
``modeling_deepseek.py`` of DeepSeek-V2-Lite): the forward pass in
``jax.numpy``, one sequence at a time and one layer at a time, every
matmul at ``Precision.HIGHEST``.  It imports nothing of the program.

It also makes the weights, from a seed, in the layout the program reads
(the leading dense layers and the MoE layers each stacked on a leading
axis, f32 as the program stores them), so the benchmark hands the same
arrays to the program and to this reference.

Layer equations (pre-norm, RMSNorm eps 1e-6; H heads, no-rope/rope
query-key dims dn/dr, value dim dv, latent rank r):

    h = rmsnorm(x) * norm1
    q = h Wq                       per head [q_nope (dn), q_pe (dr)]
    [c, k_pe] = h Wkva;  c = rmsnorm(c) * norm_kv
    [k_nope, v] = c Wkvb           per head (dn, dv)
    q_pe, k_pe = rope(q_pe), rope(k_pe)
        pairs (2i, 2i+1) rotated by t * f_i, f the YaRN frequencies:
        f_i = b_i (1 - g_i) + (b_i / s) g_i, b_i = theta^(-2i/dr), g_i a
        linear ramp from 0 at dim floor(d(beta_fast)) to 1 at
        ceil(d(beta_slow)), d(n) = dr ln(L0 / (2 pi n)) / (2 ln theta);
        cos and sin times m(s, mscale) / m(s, mscale_all_dim),
        m(s, a) = 0.1 a ln s + 1
    x = x + softmax([q_nope, q_pe] . [k_nope, k_pe] * scale, causal) v Wo
        scale = (dn + dr)^-1/2 * m(s, mscale_all_dim)^2
    h = rmsnorm(x) * norm2
    leading dense layers:  x = x + (silu(h Wg) * (h Wu)) Wd
    MoE layers:  p = softmax(h Wr) over all E experts (f32);
                 (w, e) = the top-k of p  (renormalised if norm_topk_prob)
                 x = x + sum_k w_k [e_k held] SwiGLU_{e_k}(h)
                       + SwiGLU_shared(h)
    logits = rmsnorm(x) * norm_f  W_head

Per-head (not absorbed) attention: the latent is expanded to every head's
keys and values.  Each held expert is computed on every token and weighted
by its router weight, zero where the token did not pick it.

Departure, the chip's share (the benchmark's deployment): the layer holds
the experts ``first_held_expert`` .. ``+ n_held`` of the router's
``n_experts``; the experts held on other chips add nothing here, as in
the program.  With every expert held it is the published layer.

``quantize=True`` is the control: the same forward pass with every matmul
operand rounded to float8 (e4m3, one scale per tensor), the precision
below the bf16 that the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _held(cfg) -> int:
    return cfg.held_experts or cfg.n_experts


def _attn_shapes(cfg, L) -> dict:
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {"wq": (L, D, H * (dn + dr)), "wkv_a": (L, D, r + dr),
            "norm_kv": (L, r), "wkv_b": (L, r, H * (dn + dv)),
            "wo": (L, H * dv, D)}


def param_shapes(cfg) -> dict:
    """Leaf shapes, in the program's layout."""
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
    Fe, E = cfg.moe_d_ff, _held(cfg)
    L0 = cfg.first_dense_layers
    L1 = cfg.n_layers - L0
    S = cfg.n_shared_experts * Fe
    moe = {"router": (L1, D, cfg.n_experts), "w_gate": (L1, E, D, Fe),
           "w_up": (L1, E, D, Fe), "w_down": (L1, E, Fe, D)}
    if S:
        moe["shared"] = {"w_gate": (L1, D, S), "w_up": (L1, D, S),
                         "w_down": (L1, S, D)}
    p = {"embed": (V, D), "norm_f": (D,), "lm_head": (D, V),
         "layers": {"attn": _attn_shapes(cfg, L1), "ffn": moe,
                    "norm1": (L1, D), "norm2": (L1, D)}}
    if L0:
        p["dense_layers"] = {
            "attn": _attn_shapes(cfg, L0),
            "ffn": {"w_gate": (L0, D, F), "w_up": (L0, D, F), "w_down": (L0, F, D)},
            "norm1": (L0, D), "norm2": (L0, D)}
    return p


def init_params(key, cfg) -> dict:
    """f32 weights from ``key``: matrices N(0, 2 / (fan_in + fan_out)),
    the embedding N(0, 0.02^2), norm scales 1 + N(0, 0.1^2).  Jit it:
    every leaf is made on the device."""
    shapes = param_shapes(cfg)
    is_leaf = lambda s: isinstance(s, tuple)  # noqa: E731
    leaves, tree = jax.tree.flatten(shapes, is_leaf=is_leaf)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_leaf)[0]]
    out = []
    for k, shape, path in zip(jax.random.split(key, len(leaves)), leaves, paths):
        z = jax.random.normal(k, shape, jnp.float32)
        name = path.split("'")[-2]
        if name == "embed":
            out.append(z * 0.02)
        elif name.startswith("norm"):
            out.append(1.0 + 0.1 * z)
        else:
            out.append(z * (2.0 / (shape[-2] + shape[-1])) ** 0.5)
    return jax.tree.unflatten(tree, out)


def _q8(x):
    """Round to float8 e4m3 with one scale for the tensor, back in f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(eq, a, b, quantize):
    if quantize:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mscale(s, a):
    return 1.0 if s <= 1 else 0.1 * a * math.log(s) + 1.0


def yarn_frequencies(cfg):
    """The rope dims' frequencies f_i (dr / 2,)."""
    dr, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    base = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    s = cfg.yarn_factor
    if s <= 1:
        return base

    def d(n):
        return dr * math.log(cfg.yarn_original_max_pos / (2 * math.pi * n)) / (2 * math.log(theta))

    lo = max(math.floor(d(cfg.yarn_beta_fast)), 0)
    hi = min(math.ceil(d(cfg.yarn_beta_slow)), dr - 1)
    g = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3), 0, 1)
    return base * (1 - g) + base / s * g


def _rope(x, cfg):
    """x (T, ..., dr): rotate the pairs (2i, 2i+1) by position."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * yarn_frequencies(cfg)
    m = 1.0
    if cfg.yarn_factor > 1:
        m = _mscale(cfg.yarn_factor, cfg.yarn_mscale) / _mscale(cfg.yarn_factor,
                                                                cfg.yarn_mscale_all_dim)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    cos = cos.reshape((T,) + (1,) * (x.ndim - 2) + cos.shape[-1:])
    sin = sin.reshape(cos.shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def softmax_scale(cfg) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor > 1 and cfg.yarn_mscale_all_dim:
        s *= _mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return s


def _attention(a, h, cfg, quantize):
    T = h.shape[0]
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    q = _mm("td,de->te", h, a["wq"], quantize).reshape(T, H, -1)
    ckv = _mm("td,de->te", h, a["wkv_a"], quantize)
    c = _rmsnorm(ckv[:, :r], a["norm_kv"])
    k_pe = _rope(ckv[:, r:], cfg)                                     # (T, dr)
    kv = _mm("tr,re->te", c, a["wkv_b"], quantize).reshape(T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe[:, None], (T, H, k_pe.shape[-1]))], -1)
    s = _mm("thd,shd->hts", q, k, quantize) * softmax_scale(cfg)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm("hts,shd->thd", w, kv[..., dn:], quantize).reshape(T, H * dv)
    return _mm("te,ed->td", o, a["wo"], quantize)


def _swiglu(h, wg, wu, wd, quantize):
    g = jax.nn.silu(_mm("td,df->tf", h, wg, quantize))
    return _mm("tf,fd->td", g * _mm("td,df->tf", h, wu, quantize), wd, quantize)


def _moe(f, h, cfg, quantize):
    p = jax.nn.softmax(_mm("td,de->te", h, f["router"], quantize), axis=-1)
    w, e = jax.lax.top_k(p, cfg.top_k)                                # exact
    if cfg.norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    w = w * cfg.routed_scaling
    y = jnp.zeros_like(h)
    if cfg.n_shared_experts:
        s = f["shared"]
        y = y + _swiglu(h, s["w_gate"], s["w_up"], s["w_down"], quantize)
    for j in range(_held(cfg)):
        gate = jnp.sum(jnp.where(e == cfg.first_held_expert + j, w, 0.0), -1)   # (T,)
        y = y + gate[:, None] * _swiglu(h, f["w_gate"][j], f["w_up"][j],
                                         f["w_down"][j], quantize)
    return y


@functools.partial(jax.jit, static_argnames=("cfg", "dense", "quantize"))
def _layer(p, x, cfg, dense, quantize):
    x = x + _attention(p["attn"], _rmsnorm(x, p["norm1"]), cfg, quantize)
    h = _rmsnorm(x, p["norm2"])
    f = p["ffn"]
    if dense:
        return x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"], quantize)
    return x + _moe(f, h, cfg, quantize)


@functools.partial(jax.jit, static_argnames=("quantize",))
def _head(params, x, quantize):
    return _mm("td,dv->tv", _rmsnorm(x, params["norm_f"]), params["lm_head"], quantize)


def logits(params, tokens, cfg, at, quantize: bool = False) -> jax.Array:
    """f32 logits (len(at), V) of one sequence ``tokens`` (T,) at the
    positions ``at``: the logits that predict the token after each."""
    x = jnp.take(params["embed"], tokens, axis=0)
    for key, dense in (("dense_layers", True), ("layers", False)):
        if key not in params:
            continue
        n = jax.tree.leaves(params[key])[0].shape[0]
        for i in range(n):
            layer = jax.tree.map(lambda a, i=i: a[i], params[key])
            x = _layer(layer, x, cfg, dense, quantize)
    return _head(params, x[jnp.asarray(at)], quantize)
