"""Tests for ``repro.runtime.spans``: span nesting and self time, the
record bound, JAX's compile counters, the spans of ``generate`` and of the
ISA compiler's pipeline (in memory and in a profiler trace), the named
scopes in the compiled decode step and GEMM, and the span summary that
``repro.launch.serve`` prints."""
from __future__ import annotations

import json
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core import kernels_ir as K
from repro.compile.driver import compile_program
from repro.kernels.gemm import gemm
from repro.launch import serve
from repro.models import build_model
from repro.runtime import spans


def _window(fn):
    """Run ``fn``; return its result and the spans recorded meanwhile."""
    t0 = time.perf_counter()
    out = fn()
    return out, spans.records(t0, time.perf_counter())


def _op_scopes(hlo_text: str) -> set:
    """Every component of every ``op_name`` in compiled HLO text."""
    out = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        out.update(name.split("/"))
    return out


@pytest.fixture(scope="module")
def olmo():
    cfg = get_smoke_config("olmo-1b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    return model, params, {"tokens": tokens}


def test_span_nesting_root_and_self_time():
    def run():
        with spans.span("outer", k=1) as outer:
            with spans.span("inner") as inner:
                time.sleep(0.02)
            with spans.span("inner"):
                with spans.span("leaf"):
                    pass
            time.sleep(0.01)
        return outer, inner

    (outer, inner), recs = _window(run)
    assert [r.name for r in recs] == ["inner", "leaf", "inner", "outer"]
    assert outer.parent is None and outer.root == outer.id
    assert outer.attrs == {"k": 1}
    assert all(r.root == outer.id for r in recs)
    assert inner.parent == outer.id and recs[1].parent == recs[2].id
    s = spans.summarize(recs)
    assert s["inner"]["count"] == 2 and s["outer"]["count"] == 1
    assert s["outer"]["self_s"] == pytest.approx(
        outer.seconds - s["inner"]["seconds"])
    assert 0.01 <= s["outer"]["self_s"] < outer.seconds - 0.02
    assert s["leaf"]["self_s"] == s["leaf"]["seconds"]


def test_span_records_are_bounded():
    t0 = time.perf_counter()
    for i in range(spans.MAX_RECORDS + 5):
        with spans.span("flood", i=i):
            pass
    recs = spans.records(t0)
    assert len(recs) == spans.MAX_RECORDS
    assert recs[0].attrs["i"] == 5          # the oldest went first


def test_span_closes_on_error():
    with pytest.raises(ValueError):
        with spans.span("fails"):
            raise ValueError
    with spans.span("after") as after:
        pass
    assert after.parent is None


def test_fresh_jit_adds_one_lowering_to_innermost_span():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    x = jnp.arange(7.0)

    def run():
        with spans.span("parent") as parent:
            with spans.span("child") as child:
                f(x).block_until_ready()
        return parent, child

    (parent, child), recs = _window(run)
    assert child.counters["lowerings"] == 1
    assert child.counters["compiles"] == 1
    assert child.counters["lowerings_s"] > 0
    assert "lowerings" not in parent.counters
    assert spans.inclusive(recs)[parent.id]["lowerings"] == 1
    # a second call hits JAX's own cache: nothing is lowered
    (_, again), _ = _window(run)
    assert "lowerings" not in again.counters


def test_generate_spans(olmo):
    model, params, batch = olmo
    max_new = 4
    serve.generate(model, params, batch, max_new)       # warm: steady state next
    _, recs = _window(lambda: serve.generate(model, params, batch, max_new)[0]
                      .block_until_ready())
    roots = [r for r in recs if r.parent is None]
    assert [r.name for r in roots] == ["generate"]
    assert roots[0].attrs == {"batch": 2, "prompt_len": 8, "max_new": max_new}
    by = {}
    for r in recs:
        by.setdefault(r.name, []).append(r)
    assert len(by["generate.prefill"]) == 1
    (decode,) = by["generate.decode"]
    assert decode.attrs["steps"] == max_new - 1
    steps = by["generate.decode_step"]
    assert [r.attrs["step"] for r in steps] == list(range(1, max_new))
    assert all(r.parent == decode.id for r in steps)
    # the prefill and the decode step were compiled by the warm call: the
    # steady state lowers nothing
    inc = spans.inclusive(recs)
    assert inc[decode.id]["lowerings"] == 0
    assert inc[by["generate.prefill"][0].id]["lowerings"] == 0


def test_generate_spans_in_profiler_trace(olmo, tmp_path):
    model, params, batch = olmo
    serve.generate(model, params, batch, 3)
    with jax.profiler.trace(str(tmp_path)):
        serve.generate(model, params, batch, 3)[0].block_until_ready()
    (path,) = tmp_path.rglob("*.xplane.pb")
    profile = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for plane in profile.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"generate", "generate.prefill", "generate.decode",
            "generate.decode_step"} <= names


def test_decode_step_named_scopes(olmo):
    model, params, batch = olmo
    cache, _ = model.prefill(params, batch, max_len=12)
    text = jax.jit(model.decode_step).lower(
        params, cache, batch["tokens"][:, -1], jnp.int32(8)).compile().as_text()
    assert {"embed", "weight_cast", "attn", "kv_update", "ffn", "final_norm",
            "head"} <= _op_scopes(text)


def test_prefill_named_scopes(olmo):
    model, params, batch = olmo
    text = jax.jit(lambda p, b: model.prefill(p, b, max_len=12)).lower(
        params, batch).compile().as_text()
    assert {"embed", "weight_cast", "attn", "kv_update", "ffn", "final_norm",
            "head"} <= _op_scopes(text)


def test_gemm_kernel_name_and_scopes():
    a = jnp.ones((100, 70), jnp.float32)
    b = jnp.ones((70, 50), jnp.float32)
    text = jax.jit(lambda a, b: gemm(a, b, block=(64, 128, 128), interpret=True)
                   ).lower(a, b).as_text(debug_info=True)
    assert {"isam_gemm", "isam_gemm.pad", "isam_gemm.crop"} <= set(
        re.findall(r"isam_gemm[\w.]*", text))


def test_pipeline_pass_spans():
    prog = K.matmul(64, 48, 32)
    _, recs = _window(lambda: compile_program(prog, use_cache=False))
    (root,) = [r for r in recs if r.name == "isam.compile"]
    assert root.parent is None and root.attrs == {"program": prog.name}
    passes = [r.name for r in recs if r.parent == root.id]
    assert passes == ["isam.map", "isam.select", "isam.schedule", "isam.verify",
                      "isam.lower"]


def test_compile_selection_records_no_spans():
    # the search and fabric loops compile one selection per candidate
    from repro.compile.driver import compile_selection, select_program
    from repro.core import instructions as I
    from repro.core.sysgraph import tpu_v5e
    prog = K.matmul(64, 48, 32)
    sel = select_program(prog, [I.mxu_matmul()], allow_transforms=False)
    art, recs = _window(lambda: compile_selection(sel, tpu_v5e(1)))
    assert art.schedule is not None
    assert not [r for r in recs if r.name.startswith("isam.")]


def test_compile_cli_prints_pass_times(tmp_path, capsys):
    from repro.compile.__main__ import main
    out = tmp_path / "report.json"
    assert main(["--kernel", "gemm", "--shape", "64x48x40", "--no-cache",
                 "--json", str(out)]) == 0
    printed = capsys.readouterr().out
    assert re.search(r"compile [\d.]+ ms: schedule [\d.]+, verify [\d.]+, "
                     r"lower [\d.]+", printed)
    (row,) = json.loads(out.read_text())["rows"]
    ms = row["compile_ms"]
    assert set(ms) == {"compile", "schedule", "verify", "lower"}
    assert ms["compile"] >= ms["schedule"] + ms["verify"] + ms["lower"]


def test_serve_main_prints_span_summary(monkeypatch, capsys):
    # the persistent compile cache stays off for the rest of the test process
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    serve.main(["--arch", "olmo-1b", "--smoke", "--batch", "2",
                "--prompt-len", "8", "--gen", "3"])
    record = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "wall_s" not in record and "tok_per_s" not in record
    s = record["spans"]
    assert s["generate"]["count"] == 1
    assert s["generate.decode"]["count"] == 1
    assert s["generate.decode_step"]["count"] == 2
    assert s["generate.cast_weights"]["count"] == 1
    # cold: each compiled entry point lowers once, at its first call
    assert s["generate.prefill"]["counters"].get("lowerings", 0) <= 1
    assert s["generate.decode"]["counters"].get("lowerings", 0) <= 1
