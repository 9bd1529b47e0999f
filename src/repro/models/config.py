"""Model configuration + shape descriptors for the assigned architectures."""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // n_heads
    qkv_bias: bool = False
    norm: str = "rmsnorm"       # rmsnorm | nonparam_ln
    tie_embeddings: bool = False
    rope_theta: float = 1e4
    sliding_window: int = 0     # >0: SWA (mixtral)
    # YaRN rope scaling (deepseek-v2): factor 0 = plain rope
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # multi-head latent attention (deepseek-v2): kv_lora_rank > 0 selects it
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # MoE: a softmax router over n_experts, greedy top_k, dropless
    n_experts: int = 0
    top_k: int = 0
    norm_topk_prob: bool = True     # renormalise the top-k weights
    routed_scaling: float = 1.0
    moe_d_ff: int = 0               # expert width; 0 -> d_ff
    n_shared_experts: int = 0       # one SwiGLU of width n * moe_d_ff
    first_dense_layers: int = 0     # leading layers with the dense d_ff FFN
    moe_period: int = 1         # MoE FFN every Nth layer (jamba: 2)
    # the experts this chip holds: held_experts from first_held_expert
    # (0 -> all); the router still scores all n_experts
    held_experts: int = 0
    first_held_expert: int = 0
    # hybrid (jamba): one attention layer per `attn_period`, rest mamba
    attn_period: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    # xlstm: one sLSTM block per `slstm_period`, rest mLSTM
    slstm_period: int = 0
    mlstm_proj_factor: float = 2.0
    # encoder-decoder (whisper)
    encoder_layers: int = 0
    # modality frontend stubs
    frontend_tokens: int = 0    # patches / audio frames provided pre-embedded
    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True
    loss_chunk: int = 0         # 0 = no logits chunking

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def n_held(self) -> int:
        return self.held_experts or self.n_experts

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    def scaled(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    # ---- parameter count (for MODEL_FLOPS = 6 N D) -------------------------
    def _attn_params(self) -> int:
        D, H, KV, hd = self.d_model, self.n_heads, self.n_kv_heads, self.hd
        if self.kv_lora_rank:
            r, qk = self.kv_lora_rank, self.qk_nope_head_dim + self.qk_rope_head_dim
            return (D * H * qk + D * (r + self.qk_rope_head_dim) + r
                    + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * D)
        attn = D * H * hd + 2 * D * KV * hd + H * hd * D
        if self.qkv_bias:
            attn += (H + 2 * KV) * hd
        return attn

    def param_count(self, active_only: bool = False) -> int:
        """Parameters of the published model: every expert, whatever
        ``held_experts`` says (``active_only``: the top_k routed ones)."""
        D, F, V = self.d_model, self.d_ff, self.vocab_size
        attn = self._attn_params()
        mlp_dense = 3 * D * F                    # swiglu gate/up/down
        if self.family == "hybrid" and self.attn_period:
            n_attn_layers = self.n_layers // self.attn_period
            n_mamba = self.n_layers - n_attn_layers
            di = self.mamba_expand * D
            mamba = (D * 2 * di + di * self.mamba_d_conv
                     + di * (2 * self.mamba_d_state + 1)
                     + di + di * D)
            total = n_attn_layers * attn + n_mamba * mamba
        elif self.family == "ssm":
            # xlstm mLSTM: in/out proj + block-diagonal per-head qkv + gates
            di = int(self.mlstm_proj_factor * D)
            dh = di // max(self.n_heads, 1)
            mlstm = 2 * D * di + 3 * dh * dh * self.n_heads + 2 * di + di * D
            total = self.n_layers * mlstm
        else:
            total = self.n_layers * attn
        if self.family != "ssm":
            n_moe = ((self.n_layers - self.first_dense_layers) // self.moe_period
                     if self.n_experts else 0)
            n_dense = self.n_layers - n_moe
            if n_moe:
                expert = 3 * D * self.expert_ff
                routed = self.top_k if active_only else self.n_experts
                total += n_moe * ((routed + self.n_shared_experts) * expert
                                  + D * self.n_experts)          # + router
            total += n_dense * mlp_dense
        total += 2 * D  # final norm(s)
        total += V * D * (1 if self.tie_embeddings else 2)
        if self.encoder_layers:
            total += self.encoder_layers * (attn + mlp_dense)  # encoder stack
            total += self.n_layers * attn                      # cross attention
        return int(total)


@dataclass(frozen=True)
class ShapeConfig:
    """One of the assigned input-shape cells."""
    name: str                  # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

#: archs with quadratic full attention skip long_500k (see DESIGN.md)
FULL_ATTENTION_ARCHS = {
    "olmo-1b", "qwen2-7b", "qwen1.5-32b", "qwen2.5-32b", "llava-next-34b",
    "whisper-medium", "deepseek-v2-lite",
}


def shape_applicable(arch: str, shape: str) -> bool:
    if shape == "long_500k" and arch in FULL_ATTENTION_ARCHS:
        return False
    return True
