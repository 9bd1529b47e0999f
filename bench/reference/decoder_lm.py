"""Plain float32 reference of a dense decoder LM (OLMo, Qwen2): the
forward pass in ``jax.numpy``, one sequence at a time and one layer at a
time, every matmul at ``Precision.HIGHEST``.  It imports nothing of the
program.

It also makes the weights, from a seed, in the layout the program reads
(layers stacked on a leading axis, f32 as the program stores them), so the
benchmark hands the same arrays to the program and to this reference.

Layer equations (pre-norm; RoPE on the two halves of each head; grouped
query heads share K/V head ``h // (H / KV)``):

    h = norm(x);  q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
    x = x + softmax(rope(q) rope(k)^T / sqrt(hd), causal) v Wo
    x = x + (silu(norm(x) Wg) * (norm(x) Wu)) Wd
    logits = rmsnorm(x) * norm_f  W_head

``norm`` is OLMo's non-parametric LayerNorm (eps 1e-5) or RMSNorm with a
scale (eps 1e-6).  Departure, as the program has it: the final norm is
RMSNorm with a scale for OLMo too, where OLMo's published final norm is
its non-parametric LayerNorm.

``quantize=True`` is the control: the same forward pass with every matmul
operand rounded to float8 (e4m3, one scale per tensor), the precision
below the bf16 that the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _hd(cfg) -> int:
    return cfg.head_dim or cfg.d_model // cfg.n_heads


def param_shapes(cfg) -> dict:
    """Leaf shapes, in the program's layout."""
    L, D, H, KV, F, V = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                         cfg.n_kv_heads, cfg.d_ff, cfg.vocab_size)
    hd = _hd(cfg)
    attn = {"wq": (L, D, H * hd), "wk": (L, D, KV * hd),
            "wv": (L, D, KV * hd), "wo": (L, H * hd, D)}
    if cfg.qkv_bias:
        attn.update(bq=(L, H * hd), bk=(L, KV * hd), bv=(L, KV * hd))
    layers = {"attn": attn,
              "ffn": {"w_gate": (L, D, F), "w_up": (L, D, F),
                      "w_down": (L, F, D)}}
    if cfg.norm == "rmsnorm":
        layers.update(norm1=(L, D), norm2=(L, D))
    p = {"embed": (V, D), "layers": layers, "norm_f": (D,)}
    if not cfg.tie_embeddings:
        p["lm_head"] = (D, V)
    return p


def init_params(key, cfg) -> dict:
    """f32 weights from ``key``: matrices N(0, 2 / (fan_in + fan_out)),
    the embedding N(0, 0.02^2), biases N(0, 0.02^2), norm scales
    1 + N(0, 0.1^2).  Jit it: every leaf is made on the device."""
    shapes = param_shapes(cfg)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, shape, path in zip(keys, leaves, paths):
        z = jax.random.normal(k, shape, jnp.float32)
        name = path.split("'")[-2]
        if name == "embed":
            out.append(z * 0.02)
        elif name.startswith("b"):
            out.append(z * 0.02)
        elif name.startswith("norm"):
            out.append(1.0 + 0.1 * z)
        else:
            fan_in, fan_out = shape[-2], shape[-1]
            out.append(z * (2.0 / (fan_in + fan_out)) ** 0.5)
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


def _layernorm(x, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    """x (T, H, hd); rotate the two halves of each head by position."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg", "quantize"))
def _layer(p, x, cfg, quantize):
    T, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, _hd(cfg)
    norm = ((lambda y, s: _layernorm(y)) if cfg.norm == "nonparam_ln"
            else _rmsnorm)
    a = p["attn"]
    h = norm(x, p.get("norm1"))
    q = _mm("td,de->te", h, a["wq"], quantize)
    k = _mm("td,de->te", h, a["wk"], quantize)
    v = _mm("td,de->te", h, a["wv"], quantize)
    if cfg.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(T, H, hd), cfg.rope_theta)
    k = _rope(k.reshape(T, KV, hd), cfg.rope_theta)
    v = v.reshape(T, KV, hd)
    rep = H // KV
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = _mm("thd,shd->hts", q, k, quantize) / hd ** 0.5
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("hts,shd->thd", w, v, quantize).reshape(T, H * hd)
    x = x + _mm("te,ed->td", o, a["wo"], quantize)
    f = p["ffn"]
    h = norm(x, p.get("norm2"))
    g = jax.nn.silu(_mm("td,df->tf", h, f["w_gate"], quantize))
    u = _mm("td,df->tf", h, f["w_up"], quantize)
    return x + _mm("tf,fd->td", g * u, f["w_down"], quantize)


@functools.partial(jax.jit, static_argnames=("cfg", "quantize"))
def _head(params, x, cfg, quantize):
    x = _rmsnorm(x, params["norm_f"])
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return _mm("td,dv->tv", x, w, quantize)


def logits(params, tokens, cfg, at, quantize: bool = False) -> jax.Array:
    """f32 logits (len(at), V) of one sequence ``tokens`` (T,) at the
    positions ``at``: the logits that predict the token after each."""
    x = jnp.take(params["embed"], tokens, axis=0)
    for i in range(cfg.n_layers):
        layer = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        x = _layer(layer, x, cfg, quantize)
    return _head(params, x[jnp.asarray(at)], cfg, quantize)
