"""Sharding-rule unit tests over an abstract 16x16 production mesh — no
devices required (PartitionSpec logic only)."""
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.dist.sharding import (batch_spec, cache_spec, dp_axes, param_spec,
                                 shard_dim)

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_dp_axes():
    assert dp_axes(MESH) == ("data",)
    assert dp_axes(MESH3) == ("pod", "data")


def test_shard_dim_divisibility():
    assert shard_dim(MESH, 4096, "model") == "model"
    assert shard_dim(MESH, 28, "model") is None
    assert shard_dim(MESH, 28, "model", ("data",)) is None
    assert shard_dim(MESH3, 256, ("pod", "data")) == ("pod", "data")


def test_attention_param_rules():
    cfg = get_config("qwen2-7b")
    # column-parallel qkv: FSDP on input dim, TP on output dim
    assert param_spec("layers/attn/wq", (28, 3584, 3584), MESH, cfg) \
        == P(None, "data", "model")
    # row-parallel output proj
    assert param_spec("layers/attn/wo", (28, 3584, 3584), MESH, cfg) \
        == P(None, "model", "data")
    assert param_spec("layers/norm1", (28, 3584), MESH, cfg) == P()


def test_embed_lm_head_rules():
    cfg = get_config("qwen2-7b")
    assert param_spec("embed", (152064, 3584), MESH, cfg) \
        == P("model", "data")
    assert param_spec("lm_head", (3584, 152064), MESH, cfg) \
        == P("data", "model")


def test_moe_expert_parallelism():
    cfg = get_config("phi3.5-moe-42b-a6.6b")     # 16 experts: EP over model
    spec = param_spec("layers/ffn/w_gate", (32, 16, 4096, 6400), MESH, cfg)
    assert spec == P(None, "model", "data", None)


def test_moe_tp_fallback_when_experts_dont_divide():
    cfg = get_config("mixtral-8x7b")             # 8 experts: TP fallback
    spec = param_spec("layers/ffn/w_gate", (32, 8, 4096, 14336), MESH, cfg)
    assert spec == P(None, None, "data", "model")


def test_slstm_recurrent_weight_replicated():
    cfg = get_config("xlstm-1.3b")
    assert param_spec("blocks/slstm/p/r_z", (6, 2048, 2048), MESH, cfg) \
        in (P(None, None, None), P())
    # the hoisted projections stay TP
    assert param_spec("blocks/slstm/p/w_z", (6, 2048, 2048), MESH, cfg) \
        == P(None, "data", "model")


def test_batch_specs():
    assert batch_spec("tokens", (256, 4096), MESH) == P("data", None)
    assert batch_spec("tokens", (128,), MESH) == P("data")
    # long-context batch=1: sequence sharding fallback
    assert batch_spec("tokens", (1, 524288), MESH) == P(None, "data")


def test_kv_cache_specs():
    # (L, S, KV, B, hd): batch over data, KV heads over model, else head_dim
    cfg = get_config("qwen2.5-32b")   # kv=8: heads don't divide 16
    spec = cache_spec("kv/k", (64, 32768, 8, 128, 128), MESH, cfg)
    assert spec[3] == "data"
    assert spec[2] is None and spec[4] == "model"   # head_dim sharded
    cfg2 = get_config("qwen1.5-32b")  # kv=40 -> not divisible either
    spec2 = cache_spec("kv/k", (64, 32768, 40, 128, 128), MESH, cfg2)
    assert spec2[3] == "data" and spec2[4] == "model"
    cfg3 = get_config("olmo-1b")      # kv=16 divides: heads sharded
    spec3 = cache_spec("kv/v", (16, 544, 16, 32, 128), MESH, cfg3)
    assert spec3 == P(None, None, "model", "data", None)


def test_mamba_state_specs():
    cfg = get_config("jamba-1.5-large-398b")
    spec = cache_spec("dense/h", (9, 4, 128, 16384, 16), MESH, cfg)
    assert spec[-2] == "model"        # d_inner sharded

def test_activation_rules_fallback_to_sequence():
    from repro.dist.sharding import make_activation_rules
    cfg = get_config("qwen2-7b")      # 28 heads % 16 != 0
    rules = make_activation_rules(MESH, cfg)
    s = rules("heads", (32, 32768, 28, 128))
    assert s.spec == P("data", "model", None, None)
    cfg2 = get_config("mixtral-8x7b")  # 32 heads: divisible
    rules2 = make_activation_rules(MESH, cfg2)
    s2 = rules2("heads", (256, 4096, 32, 128))
    assert s2.spec == P("data", None, "model", None)
