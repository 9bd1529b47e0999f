"""Mixture-of-experts FFN: dropless routing to the experts this layer holds.

A softmax router (in f32, at full precision on a TPU too, so that its
choices are the published model's) scores every token over all
``n_experts``; greedy
top-k picks its experts, whose weights are renormalised when
``norm_topk_prob`` and scaled by ``routed_scaling``.  The layer holds the
experts ``first_held_expert`` .. ``+ n_held`` (all by default): the
(token, k) assignments to them are sorted by expert, their token rows
gathered, and the SwiGLU runs as three grouped matmuls
(``jax.lax.ragged_dot``) over the held experts, so no token is dropped
and each expert computes only the rows routed to it.  Assignments to
experts held elsewhere sort after every group and add nothing: the layer
returns its own experts' part of the result, which expert parallelism
sums over the chips.  ``n_shared_experts`` shared experts (one SwiGLU of
their summed width) see every token and are added once.

Named scopes: ``moe.route`` (router, top-k, sort), ``moe.experts`` (the
grouped matmuls and the weighted combine), ``moe.shared``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import init_dense, swiglu

#: tokens per dispatch group: a long prefill is routed in groups so the
#: gathered rows (group x top_k) stay small
MOE_GROUP = 8192


def init_moe_params(rng, cfg: ModelConfig, dtype) -> dict:
    """Router over all experts; distinct weights for each held expert."""
    D, F, E = cfg.d_model, cfg.expert_ff, cfg.n_held
    ks = jax.random.split(rng, 5)

    def stacked(k, fan_in, fan_out):
        return jax.vmap(lambda kk: init_dense(kk, fan_in, fan_out, dtype))(
            jax.random.split(k, E))

    p = {"router": init_dense(ks[0], D, cfg.n_experts, jnp.float32),
         "w_gate": stacked(ks[1], D, F),
         "w_up": stacked(ks[2], D, F),
         "w_down": stacked(ks[3], F, D)}
    if cfg.n_shared_experts:
        S = cfg.n_shared_experts * F
        kg, ku, kd = jax.random.split(ks[4], 3)
        p["shared"] = {"w_gate": init_dense(kg, D, S, dtype),
                       "w_up": init_dense(ku, D, S, dtype),
                       "w_down": init_dense(kd, S, D, dtype)}
    return p


def _routed(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The held experts' part of the layer for tokens x (N, D)."""
    N, D = x.shape
    E, K = cfg.n_held, cfg.top_k
    with jax.named_scope("moe.route"):
        logits = jnp.dot(x.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)   # f32 on a TPU too
        probs = jax.nn.softmax(logits, -1)
        gate, idx = jax.lax.top_k(probs, K)                      # (N, K)
        if cfg.norm_topk_prob:
            gate = gate / gate.sum(-1, keepdims=True)
        gate = gate * cfg.routed_scaling
        local = idx - cfg.first_held_expert
        held = (local >= 0) & (local < E)
        expert = jnp.where(held, local, E).reshape(N * K)        # E: elsewhere
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.bincount(expert, length=E + 1)[:E]
    with jax.named_scope("moe.experts"):
        xs = x[order // K]                                       # (N*K, D)
        h = (jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], sizes))
             * jax.lax.ragged_dot(xs, p["w_up"], sizes))
        ys = jax.lax.ragged_dot(h, p["w_down"], sizes)
        # back to (token, k) order; rows outside every group are dropped
        ys = ys[jnp.argsort(order)].reshape(N, K, D)
        ys = jnp.where(held[..., None], ys.astype(jnp.float32), 0.0)
        return (gate[..., None] * ys).sum(1).astype(x.dtype)


def moe_ffn(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, T, D) -> (B, T, D): the held experts' part plus the shared
    experts."""
    B, T, D = x.shape
    N = B * T
    xf = x.reshape(N, D)
    n = min(MOE_GROUP, N)
    while N % n:
        n -= 1
    if n == N:
        y = _routed(p, xf, cfg)
    else:
        y = jax.lax.map(lambda xg: _routed(p, xg, cfg),
                        xf.reshape(N // n, n, D)).reshape(N, D)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            s = p["shared"]
            y = y + swiglu(xf, s["w_gate"], s["w_up"], s["w_down"])
    return y.reshape(B, T, D)
