"""Operations and bytes that DeepSeek-V2's serving needs, from its shapes:
multi-head latent attention and a layer of routed experts of which this
chip holds ``n_held`` of ``n_experts``.

As in ``flops.py``, they count the work the computation needs, whatever
implements it: a matmul is 2 * m * n * k operations, causal attention
counts the pairs (query, key) with key <= query, and bf16 operands are
read once.  Prefill counts the per-head form (the latent expanded to every
head's keys and values), a decode step the absorbed form (attention over
the latent cache).  Routed-expert work counts the expected assignments to
held experts, top_k * n_held / n_experts per token, and a step's expert
bytes the held experts it is expected to touch, n_held * (1 - (1 - top_k /
n_experts)^tokens), with the routed token rows in and out.  The program
routes a long prefill in dispatch groups (``models/moe.MOE_GROUP`` tokens:
16 groups at 32 x 4096) and reads the held experts' weights once per
group; the counts take them once per call, the work's own need.  At the
cell's prefill that leaves out 15 of 16 weight reads, 0.34 ms each per
layer at the HBM bandwidth against 1.1 ms of a group's operations at the
peak rate, so the call stays compute-bound either way.
"""
from __future__ import annotations

from bench.flops import BF16, causal_pairs, roofline_s


def _held(cfg) -> int:
    return cfg.held_experts or cfg.n_experts


def attn_matmul_params(cfg) -> int:
    """Weights of one layer's attention that take part in a matmul per
    token: q, the latent and its key, the latent's up-projection (in the
    absorbed form: the query's and the output's absorption, as many), o."""
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D


def prefill_pair_flops(cfg) -> int:
    """Per (query, key) pair and layer, per-head form: q.k over dn + dr and
    w.v over dv, every head."""
    return 2 * cfg.n_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim)


def decode_pair_flops(cfg) -> int:
    """Per (query, key) pair and layer, absorbed form: the latent score
    (r), the rope score (dr) and the weighted latent (r), every head."""
    return 2 * cfg.n_heads * (2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim)


def expert_params(cfg) -> int:
    return 3 * cfg.d_model * cfg.moe_d_ff


def held_share(cfg) -> float:
    """Expected assignments to held experts per token."""
    return cfg.top_k * _held(cfg) / cfg.n_experts


def ffn_token_flops(cfg, moe: bool) -> float:
    """One layer's FFN operations per token: the dense SwiGLU, or the
    router, the shared experts and the expected held-expert work."""
    D = cfg.d_model
    if not moe:
        return 2 * 3 * D * cfg.d_ff
    return 2 * (D * cfg.n_experts + expert_params(cfg) * (cfg.n_shared_experts
                                                          + held_share(cfg)))


def _layers(cfg) -> tuple[int, int]:
    return cfg.first_dense_layers, cfg.n_layers - cfg.first_dense_layers


def token_flops(cfg) -> float:
    """Every layer's projections and FFN for one token."""
    L0, L1 = _layers(cfg)
    attn = 2 * attn_matmul_params(cfg) * cfg.n_layers
    return attn + L0 * ffn_token_flops(cfg, False) + L1 * ffn_token_flops(cfg, True)


def prefill_flops(cfg, B: int, T: int) -> float:
    """Prefill of B prompts of T tokens, logits at the last position."""
    return (B * T * token_flops(cfg)
            + prefill_pair_flops(cfg) * causal_pairs(T) * cfg.n_layers * B
            + 2 * cfg.d_model * cfg.vocab_size * B)


def decode_step_flops(cfg, B: int, pos: int) -> float:
    """One decode step of B sequences whose new token sits at ``pos``."""
    return B * (token_flops(cfg) + decode_pair_flops(cfg) * (pos + 1) * cfg.n_layers
                + 2 * cfg.d_model * cfg.vocab_size)


def decode_step_bytes(cfg, B: int, pos: int) -> float:
    """Bytes one bf16 decode step needs: every layer's attention weights,
    the dense layers' SwiGLU, each MoE layer's router (f32, as the program
    reads it), shared experts and the held experts that B tokens are
    expected to touch, the head read once; the embedding rows of B tokens;
    the latent cache and roped key read for pos + 1 positions and their
    new row written."""
    L0, L1 = _layers(cfg)
    D, r, dr = cfg.d_model, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    weights = (cfg.n_layers * attn_matmul_params(cfg) + L0 * 3 * D * cfg.d_ff
               + L1 * (cfg.n_shared_experts + experts_touched(cfg, B)) * expert_params(cfg)
               + D * cfg.vocab_size + B * D)
    cache = cfg.n_layers * B * (pos + 2) * (r + dr)
    return BF16 * (weights + cache) + 4 * L1 * D * cfg.n_experts


def generate_decode_bytes(cfg, B: int, T: int, new_tokens: int) -> float:
    """Bytes of the decode steps of one ``generate`` call."""
    return sum(decode_step_bytes(cfg, B, T + i - 1) for i in range(1, new_tokens))


def generate_flops(cfg, B: int, T: int, new_tokens: int) -> float:
    return prefill_flops(cfg, B, T) + sum(
        decode_step_flops(cfg, B, T + i - 1) for i in range(1, new_tokens))


# --------------------------------------------------------------------------- #
# the routed experts of one MoE layer, per call
# --------------------------------------------------------------------------- #


def experts_touched(cfg, tokens: int) -> float:
    return _held(cfg) * (1 - (1 - cfg.top_k / cfg.n_experts) ** tokens)


def expert_flops(cfg, tokens: int) -> float:
    return 2 * expert_params(cfg) * tokens * held_share(cfg)


def expert_bytes(cfg, tokens: int) -> float:
    """The touched held experts' bf16 weights, and the routed rows read
    and written."""
    rows = tokens * held_share(cfg)
    return BF16 * (experts_touched(cfg, tokens) * expert_params(cfg) + 2 * rows * cfg.d_model)


def expert_roofline_s(cfg, B: int, T: int, new_tokens: int, peak: dict) -> float:
    """Least device time of the routed experts of one ``generate``: every
    MoE layer once over the prompt tokens, then once per decode step."""
    def call(tokens):
        return roofline_s(expert_flops(cfg, tokens), expert_bytes(cfg, tokens), peak)
    return _layers(cfg)[1] * (call(B * T) + (new_tokens - 1) * call(B))


# --------------------------------------------------------------------------- #
# absorbed attention over the latent cache, per decode step and layer
# --------------------------------------------------------------------------- #


def attend_flops(cfg, B: int, pos: int) -> float:
    return B * decode_pair_flops(cfg) * (pos + 1)


def attend_bytes(cfg, B: int, pos: int) -> float:
    """The latent and the roped key of pos + 1 positions, bf16."""
    return BF16 * B * (pos + 1) * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)


def attend_roofline_s(cfg, B: int, T: int, new_tokens: int, peak: dict) -> float:
    """Least device time of the decode steps' attention of one
    ``generate``, every layer."""
    return cfg.n_layers * sum(
        roofline_s(attend_flops(cfg, B, T + i - 1), attend_bytes(cfg, B, T + i - 1), peak)
        for i in range(1, new_tokens))
