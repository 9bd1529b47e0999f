"""Operations and bytes that a computation needs, from its shapes.

They count the work the computation needs, whatever implements it: causal
attention counts the pairs (query, key) with key <= query, padding to a
tile counts nothing, a matmul is 2 * m * n * k operations, and a bf16
operand or output is read or written once.  Metrics that divide by work
take it from here, so no change to the program can change what they count.
"""
from __future__ import annotations

BF16 = 2


# --------------------------------------------------------------------------- #
# GEMM
# --------------------------------------------------------------------------- #


def gemm_flops(m: int, n: int, k: int) -> int:
    return 2 * m * n * k


def gemm_bytes(m: int, n: int, k: int, itemsize: int = BF16) -> int:
    """A (m, k) and B (k, n) read once, C (m, n) written once."""
    return itemsize * (m * k + k * n + m * n)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip can take: the larger of the compute bound
    and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def gemm_bound(m: int, n: int, k: int, peak: dict) -> str:
    """Which bound binds: ``"compute"`` or ``"memory"``."""
    c = gemm_flops(m, n, k) / peak["bf16_flops_per_s"]
    b = gemm_bytes(m, n, k) / peak["hbm_bytes_per_s"]
    return "compute" if c >= b else "memory"


# --------------------------------------------------------------------------- #
# Decoder LM (dense, GQA)
# --------------------------------------------------------------------------- #


def layer_matmul_params(cfg) -> int:
    """Weights of one layer that take part in a matmul (q, k, v, o, the
    three SwiGLU matrices)."""
    D, H, KV, hd, F = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def layer_other_params(cfg) -> int:
    """Biases of one layer (read by a decode step, no matmul)."""
    return (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.hd if cfg.qkv_bias else 0


def attn_pair_flops(cfg) -> int:
    """Operations for one (query, key) pair in one layer: q.k and w.v over
    every head."""
    return 4 * cfg.n_heads * cfg.hd


def causal_pairs(T: int) -> int:
    return T * (T + 1) // 2


def prefill_flops(cfg, B: int, T: int) -> int:
    """Prefill of B prompts of T tokens, logits at the last position only
    (all that ``generate`` uses)."""
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab_size
    matmul = 2 * layer_matmul_params(cfg) * L * B * T
    attn = attn_pair_flops(cfg) * causal_pairs(T) * L * B
    head = 2 * D * V * B
    return matmul + attn + head


def decode_step_flops(cfg, B: int, pos: int) -> int:
    """One decode step of B sequences whose new token sits at ``pos``
    (it attends pos + 1 keys)."""
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab_size
    return B * (2 * layer_matmul_params(cfg) * L + attn_pair_flops(cfg) * (pos + 1) * L
                + 2 * D * V)


def decode_step_bytes(cfg, B: int, pos: int) -> int:
    """Bytes one bf16 decode step needs: every layer's weights and biases
    and the head read once, the embedding rows of B tokens, K and V read
    for pos + 1 positions, and the new K and V written."""
    L, D, V, KV, hd = cfg.n_layers, cfg.d_model, cfg.vocab_size, cfg.n_kv_heads, cfg.hd
    weights = L * (layer_matmul_params(cfg) + layer_other_params(cfg)) + D * V
    embed_rows = B * D
    kv_read = L * 2 * B * (pos + 1) * KV * hd
    kv_write = L * 2 * B * KV * hd
    return BF16 * (weights + embed_rows + kv_read + kv_write)


def generate_flops(cfg, B: int, T: int, new_tokens: int) -> int:
    """``generate`` of B prompts of T tokens and ``new_tokens`` new tokens:
    the prefill gives the first token, each decode step one more."""
    return prefill_flops(cfg, B, T) + sum(
        decode_step_flops(cfg, B, T + i - 1) for i in range(1, new_tokens))


def generate_decode_bytes(cfg, B: int, T: int, new_tokens: int) -> int:
    """Bytes of the decode steps of one ``generate`` call."""
    return sum(decode_step_bytes(cfg, B, T + i - 1) for i in range(1, new_tokens))
