"""deepseek-v2-lite [moe]: 27L d_model=2048 16H, multi-head latent
attention (kv_lora_rank 512, no q LoRA; q/k heads 128 nope + 64 rope, v
heads 128; YaRN rope, factor 40 over 4096), layer 0 a dense SwiGLU of
10944, layers 1-26 a softmax router over 64 experts of 1408, greedy top-6
unnormalised, plus 2 shared experts; vocab 102400, untied head
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite]."""
from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab_size=102400, rope_theta=1e4,
    yarn_factor=40.0, yarn_original_max_pos=4096, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64, top_k=6, norm_topk_prob=False, routed_scaling=1.0,
    moe_d_ff=1408, n_shared_experts=2, first_dense_layers=1,
)


def smoke_config() -> ModelConfig:
    return CONFIG.scaled(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
                         d_ff=96, vocab_size=128, kv_lora_rank=32,
                         qk_nope_head_dim=16, qk_rope_head_dim=8,
                         v_head_dim=16, n_experts=8, top_k=3, moe_d_ff=24,
                         remat=False)
