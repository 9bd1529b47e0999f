"""CPU tests of the DeepSeek-V2-Lite cell (``serve_batch_ep``): a smoke copy
of the cell runs correct, reads the named scopes' instructions from the
compiled programs on a traced run, gives a recorded chip trace's
operations to the program runs that hold them, and a token altered after
it was chosen, the shared experts counted twice, or the float8 control in
the program's place, comes out not correct.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_deepseek_cell.py
"""
from __future__ import annotations

import json

import jax
import pytest

from bench import control, harness, run_cell, trace_reduce
from bench.tests.smoke import BENCH, CPU, smoke_copy

CELL = "deepseek-smoke.decode"
LIKE = "deepseek-v2-lite-5l.long-decode"
#: the smoke configuration: the published latent, head and expert widths
#: and router (64 experts, top-6) around a model of width 64; 16 experts
#: held from expert 16
SMOKE = {"num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 96, "vocab_size": 128}
#: from the smoke cell's own readings on the CPU (seeds 1-8, 3e9 + 1 and
#: 3e9 + 7): the program's served logits lie within 0.018-0.023 of the
#: reference's largest (relative), the float8 control's 0.208-0.305; the
#: median position reads 0.0096-0.0105 and 0.108-0.120.  A served token
#: never exceeds twice its logits' error (limit 0: exact).
LIMITS = {"served_token_excess": 0, "logit_rel_error": 0.07, "logit_rel_error_p50": 0.035}


@pytest.fixture(autouse=True, scope="module")
def no_persistent_cache():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ds")
    dst = smoke_copy(tmp)
    doc = json.loads((BENCH / "configs" / "deepseek-v2-lite-5l.json").read_text())
    doc.update(SMOKE, first_held_expert=16)
    doc["reduced"] = doc["reduced"] + [k for k in SMOKE if k not in doc["reduced"]]
    (dst / "configs" / "deepseek-smoke.json").write_text(json.dumps(doc))
    cell = json.loads((BENCH / "workloads" / f"{LIKE}.json").read_text())
    cell["config"] = "deepseek-smoke"
    cell["traffic"].update(batch=2, prompt_len=8, new_tokens=4, check_requests=64,
                           limits=LIMITS)
    (dst / "workloads" / f"{CELL}.json").write_text(json.dumps(cell))
    bm = json.loads((tmp / "BENCHMARK.json").read_text())
    entry = next(w for w in bm["workloads"] if w["name"] == LIKE)
    bm["workloads"].append(dict(entry, name=CELL, config="deepseek-smoke"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    return dst


def run_smoke(bench_dir, trace=0, seed=3_000_000_001):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run_cell.Tracer, "summary", lambda self, attribute: None)
        return run_cell.run(["--workload", CELL, "--seed", str(seed), "--seconds", "2",
                             "--trace", str(trace)], bench_dir=bench_dir, **CPU)


def test_smoke_cell_runs_correct_and_reads_the_scopes(smoke, capsys):
    r = run_smoke(smoke, trace=1)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and "mfu.mla" in r["metrics"]
    assert set(r["checks"]) == {"tokens_out_of_vocab", *LIMITS}
    err = capsys.readouterr().err
    assert "prefill (jit__prefill): moe.experts" in err
    assert "decode (jit__decode_step): moe.experts" in err
    assert "traced decode: " in err


def test_scope_instructions_found_in_both_programs(smoke):
    drv = harness.load_module("drivers", "serve_batch_ep", smoke)
    cell = harness.load_cell(CELL, smoke)
    ctx, _, _ = run_cell.context(cell, 5, 0.0, True, smoke, **CPU)
    state = drv.setup(ctx)
    progs = state.programs
    assert progs["prefill"]["module"] == "jit__prefill"
    assert progs["decode"]["module"] == "jit__decode_step"
    assert all(progs[role][scope] for role in progs for scope in drv.SCOPES)
    assert ctx.model_cfg.n_held == 16 and ctx.model_cfg.first_held_expert == 16


def test_ragged_dot_kernels_count_as_moe_experts(smoke):
    """On the TPU the grouped matmul is a kernel whose op_name has lost the
    caller's scopes; it is counted under ``moe.experts``."""
    drv = harness.load_module("drivers", "serve_batch_ep", smoke)
    hlo = "\n".join([
        '  %ragged-dot-none.1 = bf16[192,1408]{1,0} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}',
        '  %fusion.3 = bf16[192,2048]{1,0} fusion(%c), kind=kLoop, '
        'metadata={op_name="jit(_decode_step)/while/body/ffn/moe.experts/gather"}',
        '  %fusion.4 = bf16[32,16,128]{2,1,0} fusion(%d), kind=kLoop, '
        'metadata={op_name="jit(_decode_step)/while/body/attn/mla.attend/dot_general"}'])
    got = drv.scope_labels(hlo)
    assert got["moe.experts"] == {"ragged-dot-none.1 bf16[192,1408]", "fusion.3 bf16[192,2048]"}
    assert got["mla.attend"] == {"fusion.4 bf16[32,16,128]"}


def test_program_times_of_a_recorded_chip_trace(smoke):
    """The chip trace that ``test_bench`` reduces (a jitted matmul, module
    ``jit__lambda``, 7 of its runs in the window): its runs and the self time
    of an instruction label are given to the program, and match the
    whole-trace reduction; a program that did not run reads nothing."""
    drv = harness.load_module("drivers", "serve_batch_ep", smoke)
    profile = trace_reduce.load(BENCH / "tests" / "data" / "v5e_tiny.xplane.pb")
    label = "convolution_tanh_fusion bf16[1024,1024]"
    progs = {"decode": {"module": "jit__lambda",
                        "labels": {"moe.experts": {label}, "mla.attend": set()}},
             "prefill": {"module": "jit__prefill",
                         "labels": {"moe.experts": {label}, "mla.attend": {label}}}}
    got = drv.program_times(profile, progs)
    whole = trace_reduce.reduce(profile)
    assert got["decode"]["runs"] == 7
    assert got["decode"]["moe.experts"] == pytest.approx(whole.op_s[label], rel=1e-9)
    assert got["decode"]["mla.attend"] == 0.0
    assert whole.busy_s <= got["decode"]["s"] < whole.window_s
    assert got["prefill"] == {"runs": 0, "s": 0.0, "moe.experts": 0.0, "mla.attend": 0.0}


def test_altered_token_is_not_correct(smoke, monkeypatch):
    import repro.launch.serve as serve
    real = serve.generate

    def generate(model, params, batch, n, **kw):
        toks, logits = real(model, params, batch, n, **kw)
        V = model.cfg.vocab_size
        return toks.at[:, -1].set((toks[:, -1] + V // 2) % V), logits
    monkeypatch.setattr(serve, "generate", generate)
    r = run_smoke(smoke)
    assert not r["correct"], r["checks"]


def test_shared_experts_counted_twice_are_not_correct(smoke, monkeypatch):
    """A fault that reaches every token moves the bulk of the positions:
    ``logit_rel_error_p50`` reads it."""
    import repro.launch.serve as serve
    real = serve.generate

    def generate(model, params, batch, n, **kw):
        layers = dict(params["layers"], ffn=dict(params["layers"]["ffn"]))
        shared = layers["ffn"]["shared"]
        layers["ffn"]["shared"] = dict(shared, w_down=2 * shared["w_down"])
        return real(model, dict(params, layers=layers), batch, n, **kw)
    monkeypatch.setattr(serve, "generate", generate)
    r = run_smoke(smoke)
    assert not r["correct"], r["checks"]
    p50 = r["checks"]["logit_rel_error_p50"]
    assert p50["value"] > p50["limit"], r["checks"]


def test_control_fails_where_the_program_passes(smoke):
    for row in control.readings(CELL, [1, 2, 3_000_000_007], True, bench_dir=smoke, **CPU):
        assert all(row["program"][k] <= v for k, v in LIMITS.items()), row
        assert any(row["control"][k] > v for k, v in LIMITS.items()), row
