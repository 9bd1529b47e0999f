"""DeepSeek-V2-Lite through the program's serving path (``build_model`` ->
``DecoderLM``: latent attention, the leading dense layer, the dropless
held-expert layer) against the plain f32 reference
``bench/reference/deepseek_v2.py``, on seeded random weights at a small
size on the CPU."""
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import deepseek_v2 as ref
from repro.configs import get_config, get_smoke_config
from repro.launch.serve import generate
from repro.models import build_model, mla, moe
from repro.models.layers import swiglu
from repro.models.moe import moe_ffn

ROOT = Path(__file__).resolve().parents[1]
ARCH = "deepseek-v2-lite"


def _cfg(dtype="float32", **kw):
    return get_smoke_config(ARCH).scaled(dtype=dtype, **kw)


def _rel(got, want):
    """Largest |got - want| over the largest |want|, per position."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float((np.abs(got - want).max(-1) / np.abs(want).max(-1)).max())


def _reference(params, toks, cfg):
    return np.stack([ref.logits(params, t, cfg, np.arange(toks.shape[1]))
                     for t in toks])


@pytest.mark.parametrize("held", [{}, {"held_experts": 4, "first_held_expert": 2}])
@pytest.mark.parametrize("split", [False, True])
def test_logits_match_reference_f32(held, split, monkeypatch):
    """The program computing in f32 is the reference's function: rel 1e-4
    (f32 rounding, measured about 1.5e-6), with all experts held and with
    one chip's share; with ``split``, prefill attention runs in query
    chunks and the experts route the tokens in dispatch groups, as at the
    benchmark's sizes."""
    if split:
        monkeypatch.setattr(mla, "SCORE_BYTES", 2 * 4 * 4 * 16 * 4)
        monkeypatch.setattr(moe, "MOE_GROUP", 8)
    cfg = _cfg(**held)
    params = ref.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    got = build_model(cfg).logits(params, {"tokens": toks})
    assert _rel(got, _reference(params, toks, cfg)) <= 1e-4


@pytest.mark.parametrize("dtype,tol", [
    # f32: rounding only (measured about 2e-6)
    ("float32", 1e-4),
    # bf16: the configuration's compute precision; weights and activations
    # rounded to 8 bits at every matmul through 3 layers at width 64, and a
    # top-3 pick that can flip on a near-tie (measured 0.032-0.073 over
    # four seeds at this size)
    ("bfloat16", 0.1),
])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_prefill_and_absorbed_decode_match_reference(dtype, tol, seed):
    """Prefill, then 10 absorbed decode steps through the latent cache
    (teacher-forced), against the reference's full forward pass."""
    cfg = _cfg(dtype, held_experts=4, first_held_expert=2)
    params = ref.init_params(jax.random.PRNGKey(seed), cfg)
    model = build_model(cfg)
    T0, n = 12, 10
    toks = jax.random.randint(jax.random.PRNGKey(100 + seed), (2, T0 + n), 0,
                              cfg.vocab_size)
    cache, last = model.prefill(params, {"tokens": toks[:, :T0]}, max_len=T0 + n)
    assert set(cache) == {"kv", "kv_dense"}
    assert cache["kv"]["latent"].shape == (2, 2, T0 + n, cfg.kv_lora_rank)
    assert cache["kv_dense"]["k_rope"].shape == (1, 2, T0 + n, cfg.qk_rope_head_dim)
    got = [last[:, -1]]
    for i in range(n):
        logits, cache = model.decode_step(params, cache, toks[:, T0 + i],
                                          jnp.int32(T0 + i))
        got.append(logits)
    want = _reference(params, toks, cfg)[:, T0 - 1:]
    assert _rel(jnp.stack(got, 1).astype(jnp.float32), want) <= tol


def test_generate_serves_the_model():
    cfg = get_smoke_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    out, logits = generate(model, params, {"tokens": toks}, 4)
    assert out.shape == (2, 4) and logits.shape == (2, 4, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


def _moe_params(cfg, key):
    p = ref.init_params(key, cfg)["layers"]["ffn"]
    return jax.tree.map(lambda a: a[0], p)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of the routed experts, with the shared expert
    counted once, give the whole layer."""
    cfg = _cfg()
    p = _moe_params(cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, cfg.d_model))
    whole = moe_ffn(p, x, cfg)
    n = cfg.n_experts // 4
    parts = []
    for i in range(4):
        share = {k: p[k][i * n:(i + 1) * n] for k in ("w_gate", "w_up", "w_down")}
        share["router"] = p["router"]
        parts.append(moe_ffn(share, x, cfg.scaled(held_experts=n, first_held_expert=i * n,
                                                   n_shared_experts=0)))
    s = p["shared"]
    total = sum(parts) + swiglu(x, s["w_gate"], s["w_up"], s["w_down"])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_no_token_dropped_under_a_router_forced_onto_one_expert():
    """Every token picks expert 0 (the router scores it far above the
    rest): each still gets its whole top-k, as in the reference, where a
    capacity of 1.25 x tokens x k / experts would drop most of them."""
    cfg = _cfg()
    p = _moe_params(cfg, jax.random.PRNGKey(5))
    p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (4, 16, cfg.d_model))) + 0.5
    got = moe_ffn(p, x, cfg)
    h = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(h @ p["router"], -1)
    assert bool((jnp.argmax(probs, -1) == 0).all())
    want = ref._moe(p, h, cfg, False)
    np.testing.assert_allclose(np.asarray(got).reshape(h.shape), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """DeepSeek-V2-Lite's rope dims (64, theta 1e4, YaRN factor 40 over
    4096, beta 32/1): the ramp runs from dim pair 10 (d(32) = 10.47) to 23
    (d(1) = 22.51); scale = 192^-1/2 (0.1 * 0.707 ln 40 + 1)^2."""
    cfg = get_config(ARCH)
    f = mla.yarn_inv_freq(cfg)
    assert f.shape == (32,)
    assert f[0] == pytest.approx(1.0)
    assert f[10] == pytest.approx(1e4 ** (-20 / 64), rel=1e-6)         # below the ramp
    assert f[16] == pytest.approx(0.01 * (7 / 13) + 0.01 / 40 * (6 / 13), rel=1e-6)
    assert f[23] == pytest.approx(1e4 ** (-46 / 64) / 40, rel=1e-6)    # past the ramp
    assert f[31] == pytest.approx(1e4 ** (-62 / 64) / 40, rel=1e-6)
    np.testing.assert_allclose(f, np.asarray(ref.yarn_frequencies(cfg)), rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert mla.softmax_scale(cfg) == pytest.approx(0.114722, rel=1e-5)
    assert ref.softmax_scale(cfg) == pytest.approx(mla.softmax_scale(cfg), rel=1e-12)


#: published key -> the registry's ModelConfig field
FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "moe_intermediate_size": "moe_d_ff",
    "n_routed_experts": "n_experts", "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "top_k", "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling",
    "first_k_dense_replace": "first_dense_layers",
}
ROPE_SCALING = {"factor": "yarn_factor", "beta_fast": "yarn_beta_fast",
                "beta_slow": "yarn_beta_slow", "mscale": "yarn_mscale",
                "mscale_all_dim": "yarn_mscale_all_dim",
                "original_max_position_embeddings": "yarn_original_max_pos"}
#: published keys whose value the program implements without a field
FIXED = {"hidden_act": "silu", "scoring_func": "softmax", "topk_method": "greedy",
         "n_group": 1, "topk_group": 1, "moe_layer_freq": 1, "q_lora_rank": None,
         "rms_norm_eps": 1e-6, "model_type": "deepseek_v2"}
#: published keys that serving at these lengths does not read
UNREAD = {"max_position_embeddings", "seq_aux"}


def test_config_file_matches_the_registry():
    doc = json.loads((ROOT / "bench/configs/deepseek-v2-lite-5l.json").read_text())
    cfg = get_config(ARCH)
    assert doc["program_arch"] == ARCH and doc["reference"] == "deepseek_v2"
    assert set(doc["reduced"]) == {"num_hidden_layers", "n_routed_experts"}
    for key, field in FIELDS.items():
        want = doc["published"][key] if key in doc["reduced"] else doc[key]
        assert getattr(cfg, field) == want, key
    for key, field in ROPE_SCALING.items():
        assert getattr(cfg, field) == doc["rope_scaling"][key], key
    assert doc["rope_scaling"]["type"] == "yarn"
    for key, value in FIXED.items():
        assert doc[key] == value, key
    known = set(FIELDS) | set(FIXED) | UNREAD | {"rope_scaling"}
    published = {k for k in doc if k not in ("program_arch", "reference", "source", "paper",
                                             "deployment", "published", "reduced",
                                             "first_held_expert", "assumed", "departures")}
    assert published == known
    # the run's configuration: the cut depth and this chip's share
    assert doc["num_hidden_layers"] == 5 and doc["n_routed_experts"] == 16
    assert doc["first_held_expert"] == 0
