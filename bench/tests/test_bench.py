"""CPU tests of the benchmark: the counts of bench/flops.py against
hand-worked shapes, the trace reduction, the f32 references against the
program at smoke size, the harness finding every cell, configuration and
metric by name (and new ones added as files alone), its refusal to measure
off the chip, and faults planted under the timed path coming out as not
correct.  Run from the repository root:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, flops, harness, run_cell, trace_reduce
from bench.tests.smoke import CPU, SMOKE_CONFIGS, smoke_copy

ROOT = Path(__file__).resolve().parents[2]
V5E = harness.peaks("TPU v5 lite")
DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(autouse=True, scope="module")
def no_persistent_cache():
    """CPU programs loaded back from JAX's persistent cache can crash this
    host (AOT results for another CPU); these tests compile afresh."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


# --------------------------------------------------------------------------- #
# flops.py against hand-worked shapes
# --------------------------------------------------------------------------- #


def test_deepbench_round_flops_and_bound():
    shapes = harness.load_cell("deepbench-gemm.bf16")["config_file"]["shapes"]
    total = sum(flops.gemm_flops(*s) for s in shapes)
    bound = sum(flops.roofline_s(flops.gemm_flops(*s), flops.gemm_bytes(*s), V5E)
                for s in shapes)
    assert round(total / 1e9, 2) == 18.07
    assert round(bound * 1e6, 1) == 174.9
    # by hand: 5124x700x2048 is 14.69 GFLOP (74.6 us at 197 TFLOP/s) and
    # 31,028,704 bytes (37.9 us at 819 GB/s): bound by compute
    assert flops.gemm_flops(5124, 700, 2048) == 14_691_532_800
    assert flops.gemm_bytes(5124, 700, 2048) == 31_028_704
    assert flops.gemm_bound(5124, 700, 2048, V5E) == "compute"
    assert [flops.gemm_bound(*s, V5E) for s in shapes].count("memory") == 7


def tiny_cfg(**kw):
    base = dict(n_layers=1, d_model=4, n_heads=2, n_kv_heads=1, head_dim=0, d_ff=8,
                vocab_size=10, qkv_bias=False)
    base.update(kw)
    return SimpleNamespace(hd=base["d_model"] // base["n_heads"], **base)


def test_decoder_counts_by_hand():
    cfg = tiny_cfg()
    # q 4x4, k and v 4x2 each, o 4x4, three 4x8 SwiGLU matrices
    assert flops.layer_matmul_params(cfg) == 16 + 8 + 8 + 16 + 96
    # 3 tokens: 2*144 per token, 6 causal pairs at 4*2*2 each, one head row
    assert flops.prefill_flops(cfg, 1, 3) == 2 * 144 * 3 + 6 * 16 + 2 * 4 * 10
    # a step at pos 3 attends 4 keys
    assert flops.decode_step_flops(cfg, 2, 3) == 2 * (2 * 144 + 16 * 4 + 2 * 40)
    # bf16: weights 144 + head 40, two embedding rows of 4, K and V of 4
    # positions and 1 kv head of 2 for 2 sequences, new K and V written
    assert flops.decode_step_bytes(cfg, 2, 3) == 2 * (184 + 8 + 2 * 2 * 4 * 2 + 2 * 2 * 2)
    assert flops.generate_flops(cfg, 1, 3, 3) == (flops.prefill_flops(cfg, 1, 3)
                                                  + flops.decode_step_flops(cfg, 1, 3)
                                                  + flops.decode_step_flops(cfg, 1, 4))


def test_qwen_long_prefill_batch_flops():
    cfg = harness.model_config(harness.load_cell("qwen2-7b-4l.long-prefill")["config_file"])
    assert round(flops.prefill_flops(cfg, 4, 2048) / 1e12, 2) == 15.76


# --------------------------------------------------------------------------- #
# trace reduction
# --------------------------------------------------------------------------- #


def ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def fake_profile(device_ops, host_spans):
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)  # noqa: E731
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[line("python", host_spans)]),
        SimpleNamespace(name="/device:TPU:0", lines=[line("XLA Ops", device_ops),
                                                      line("XLA Modules", [])]),
    ])


def test_reduce_busy_idle_attribution_and_collectives():
    prof = fake_profile(
        [ev("fusion.1", 100, 100), ev("fusion.2", 150, 100),      # overlap: busy 150
         ev("all-reduce.3", 400, 100), ev("fusion.4", 450, 20),   # exposed 80
         ev("gemm.1", 900, 200)],                                 # clipped to 900..1000
        [ev("bench.window", 0, 1000), ev("bench.prefill", 50, 300),
         ev("bench.decode", 380, 600), ev("bench.batch", 0, 1000)])
    s = trace_reduce.reduce(prof)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((150 + 100 + 100) * 1e-9)
    assert s.collective_exposed_s == pytest.approx(80e-9)
    assert s.op_name_s["gemm.1"] == pytest.approx(100e-9)
    assert s.span_busy_s["bench.prefill"] == pytest.approx(150e-9)
    assert s.span_busy_s["bench.decode"] == pytest.approx(200e-9)
    # gaps 500-900, 250-400, 0-100: the first in the decode span, the others
    # (by their middles) in the prefill span
    assert s.idle_gaps[0] == ("bench.decode", pytest.approx(400e-9))
    assert [g[0] for g in s.idle_gaps] == ["bench.decode", "bench.prefill", "bench.prefill"]
    b = s.breakdown()
    assert b["device_ops"][0][1] == pytest.approx(100e-9)
    assert dict(b["device_ops"])["all-reduce.3"] == pytest.approx(80e-9)   # less fusion.4
    assert len(b["idle_gaps"]) == 3


def test_reduce_refuses_a_trace_without_window_or_device():
    with pytest.raises(ValueError):
        trace_reduce.reduce(fake_profile([ev("x", 0, 1)], []))
    prof = fake_profile([], [ev("bench.window", 0, 10)])
    prof.planes = prof.planes[:1]
    with pytest.raises(ValueError):
        trace_reduce.reduce(prof)


def test_reduce_recorded_chip_trace():
    """A trace recorded on one TPU v5e: a jitted matmul loop inside the
    benchmark's window span, its device ops found and attributed."""
    path = DATA / "v5e_tiny.xplane.pb"
    s = trace_reduce.reduce(trace_reduce.load(path))
    facts = json.loads((DATA / "v5e_tiny.json").read_text())
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.window_s == pytest.approx(facts["window_s"], rel=1e-6)
    assert s.busy_s == pytest.approx(facts["busy_s"], rel=1e-6)
    assert s.span_busy_s.get("bench.decode", 0) > 0
    assert any(re.search(facts["op_pattern"], k) for k in s.op_s)


# --------------------------------------------------------------------------- #
# references against the program
# --------------------------------------------------------------------------- #

#: the program's logits are bf16 with bf16 activations: allow 4 ulps of the
#: largest logit (2^-5 of it); the float8 control misses it by 2x or more
LOGIT_RTOL = 2.0 ** -5


@pytest.mark.parametrize("arch,tie", [
    pytest.param("olmo-1b", False, id="olmo-1b"),
    pytest.param("olmo-1b", True, id="olmo-1b-tied"),
    pytest.param("qwen2-7b", False, id="qwen2-7b")])
def test_reference_matches_program_prefill_and_decode(arch, tie):
    from repro.configs import get_smoke_config
    from repro.launch.serve import generate
    from repro.models import build_model
    ref = harness.load_module("reference", "decoder_lm")
    cfg = get_smoke_config(arch).scaled(tie_embeddings=tie)
    params = jax.jit(ref.init_params, static_argnums=1)(jax.random.PRNGKey(5), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(6), (2, 12), 0, cfg.vocab_size)
    toks, logits = generate(build_model(cfg), params, {"tokens": prompts}, 4)
    toks, logits = np.asarray(toks), np.asarray(logits, np.float32)
    for r in range(2):
        seq = jnp.concatenate([prompts[r], toks[r, :-1]])
        at = np.arange(11, 15)
        want = np.asarray(ref.logits(params, seq, cfg, at))
        ctrl = np.asarray(ref.logits(params, seq, cfg, at, quantize=True))
        scale = np.abs(want).max(-1)
        err = (np.abs(logits[r] - want).max(-1) / scale).max()
        assert err <= LOGIT_RTOL, (arch, r, err)       # prefill (at[0]) and decode
        assert (np.abs(ctrl - want).max(-1) / scale).max() > 2 * LOGIT_RTOL


def test_reference_gemm():
    ref = harness.load_module("reference", "gemm")
    (a, b), = ref.make_operands(jax.random.PRNGKey(0), [(16, 24, 32)])
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    got = np.asarray(ref.matmul(a, b))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert np.abs(np.asarray(ref.matmul(a, b, quantize=True)) - want).max() \
        > 1e-2 * np.abs(want).max()


# --------------------------------------------------------------------------- #
# the harness and BENCHMARK.json
# --------------------------------------------------------------------------- #

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_to_its_schema():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bm) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"] and bm["command"][1].startswith("bench/")
    names = [c["name"] for c in bm["configs"]] + [w["name"] for w in bm["workloads"]] \
        + [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(set(names)) == len(names) and all(NAME.match(n) for n in names)
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        doc = harness.load_json(ROOT / c["file"])
        assert doc["reduced"] == c["reduced"] and doc["source"] == c["source"]
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.25 for m in e2e.values())
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["name"] == f"{w['config']}.{w['traffic']}"
        reported = [m for m in e2e.values() if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2
        assert any(w["name"] in m["workloads"] for m in bm["per_layer"])
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bm["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])


def test_every_cell_configuration_driver_and_metric_resolves():
    bm = harness.benchmark()
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        doc = cell["config_file"]
        if doc["program_arch"]:
            harness.model_config(doc)
        harness.load_module("reference", doc["reference"])
        drv = harness.load_module("drivers", cell["driver"])
        for fn in ("setup", "window", "release", "check", "minimal", "reading"):
            assert callable(getattr(drv, fn))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    with pytest.raises(KeyError):
        harness.load_cell("no-such.cell")
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")


def test_a_config_drifting_from_the_program_is_refused():
    doc = dict(harness.load_cell("olmo-1b.batch-decode")["config_file"])
    doc["hidden_size"] = 1024
    with pytest.raises(ValueError):
        harness.model_config(doc)


def test_refuses_to_measure_off_the_chip(capsys):
    rc = run_cell.main(["--workload", "deepbench-gemm.bf16", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run_cell.py", "--workload",
                        "olmo-1b.batch-decode", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def run_smoke(bench_dir, cell, trace=0, seed=3_000_000_001, seconds=2):
    """A run of a smoke cell on the CPU.  A CPU trace has no device plane,
    so the traced window's reduction reads nothing."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run_cell.Tracer, "summary", lambda self, attribute: None)
        return run_cell.run(["--workload", cell, "--seed", str(seed), "--seconds",
                             str(seconds), "--trace", str(trace)],
                            bench_dir=bench_dir, **CPU)


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    return smoke_copy(tmp_path_factory.mktemp("smoke"))


def test_cell_configuration_and_metric_added_by_files_alone(tmp_path):
    b = smoke_copy(tmp_path)      # smoke configurations and cells: files only
    (b / "metrics" / "dummy.batches.py").write_text(
        "def read(r):\n    return float(len(r.counts['units']))\n")
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "dummy.batches", "unit": "batches", "better": "higher",
                            "source": "host_clock", "layer": "entry point",
                            "moves": "output_tok_s", "workloads": ["olmo-smoke.decode"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    r0 = run_smoke(b, "olmo-smoke.decode", trace=0)
    assert r0["correct"] and set(r0["metrics"]) == {"output_tok_s", "setup_s"}
    assert list(r0)[-1] == "checks"
    r1 = run_smoke(b, "olmo-smoke.decode", trace=1)
    assert r1["correct"] and r1["metrics"]["dummy.batches"]["value"] >= 1
    assert {"compile_share.decode", "mfu.decode"} <= set(r1["metrics"])


@pytest.mark.parametrize("cell", ["qwen-smoke.prefill", "gemm-smoke.bf16"])
def test_smoke_cells_run_correct(smoke, cell):
    r = run_smoke(smoke, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert {"setup_s"} < set(r["metrics"])


# --------------------------------------------------------------------------- #
# planted faults and the control
# --------------------------------------------------------------------------- #


def _token_altered(monkeypatch):
    import repro.launch.serve as serve
    real = serve.generate

    def generate(model, params, batch, n, **kw):
        toks, logits = real(model, params, batch, n, **kw)
        V = model.cfg.vocab_size
        return toks.at[:, -1].set((toks[:, -1] + V // 2) % V), logits
    monkeypatch.setattr(serve, "generate", generate)


def _half_batch(monkeypatch):
    import repro.launch.serve as serve
    real = serve.generate

    def generate(model, params, batch, n, **kw):
        half = batch["tokens"].shape[0] // 2
        toks, logits = real(model, params, {"tokens": batch["tokens"][:half]}, n, **kw)
        return jnp.concatenate([toks, toks]), jnp.concatenate([logits, logits])
    monkeypatch.setattr(serve, "generate", generate)


def _state_unchanged(monkeypatch):
    from repro.models.transformer import DecoderLM
    real = DecoderLM.decode_step

    def decode_step(self, params, cache, tokens, pos):
        logits, _ = real(self, params, cache, tokens, pos)
        return logits, cache
    monkeypatch.setattr(DecoderLM, "decode_step", decode_step)


def _answer_altered(monkeypatch):
    import repro.kernels.gemm as g
    real = g.gemm

    def gemm(a, b, block=None, interpret=False):
        out = real(a, b, block=block, interpret=interpret)
        return out.at[0, 0].add(jnp.max(jnp.abs(out)))
    monkeypatch.setattr(g, "gemm", gemm)


def _gemm_half_rows(monkeypatch):
    import repro.kernels.gemm as g
    real = g.gemm

    def gemm(a, b, block=None, interpret=False):
        out = real(a, b, block=block, interpret=interpret)
        return out.at[out.shape[0] // 2:].set(0)
    monkeypatch.setattr(g, "gemm", gemm)


@pytest.mark.parametrize("cell,fault", [
    ("olmo-smoke.decode", _token_altered),
    ("olmo-smoke.decode", _half_batch),
    ("olmo-smoke.decode", _state_unchanged),
    ("qwen-smoke.prefill", _token_altered),
    ("qwen-smoke.prefill", _half_batch),
    ("gemm-smoke.bf16", _answer_altered),
    ("gemm-smoke.bf16", _gemm_half_rows),
])
def test_planted_fault_is_not_correct(smoke, monkeypatch, cell, fault):
    fault(monkeypatch)
    r = run_smoke(smoke, cell)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", ["olmo-smoke.decode", "qwen-smoke.prefill",
                                  "gemm-smoke.bf16"])
def test_control_fails_where_the_program_passes(smoke, cell):
    """The float8 control in the program's place fails the cell's limits on
    every seed, where the program passes them (smoke sizes, CPU)."""
    limits = harness.load_cell(cell, smoke)["traffic"]["limits"]
    for row in control.readings(cell, [1, 2, 3_000_000_007], True, bench_dir=smoke, **CPU):
        assert all(row["program"][k] <= v for k, v in limits.items()), row
        assert any(row["control"][k] > v for k, v in limits.items()), row


def test_smoke_configs_are_listed_as_reduced(smoke):
    for name in SMOKE_CONFIGS:
        doc = harness.load_json(smoke / "configs" / f"{name}.json")
        harness.model_config(doc)
