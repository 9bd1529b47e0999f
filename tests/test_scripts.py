"""Tests for the operator scripts that read the program's spans and named
scopes: ``scripts/idle_gaps.py`` on a fake profile, ``scripts/scope_ops.py``
on the compiled decode step."""
from __future__ import annotations

import importlib.util
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from repro.runtime.spans import Record

SCRIPTS = Path(__file__).resolve().parent.parent / "scripts"


def _load(name):
    spec = importlib.util.spec_from_file_location(name, SCRIPTS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ev(name, t0, t1):
    return NS(name=name, start_ns=round(t0 * 1e9), duration_ns=round((t1 - t0) * 1e9))


@pytest.fixture(scope="module")
def idle_gaps():
    return _load("idle_gaps")


@pytest.fixture
def traced():
    """Two decode steps on ``perf_counter`` and a profile of them whose
    clock runs 100 s ahead; the device idles between its two ops."""
    recs = [Record(1, None, 1, "generate", 10.0, 12.0),
            Record(2, 1, 1, "generate.decode_step", 10.5, 11.0, attrs={"step": 3}),
            Record(3, 1, 1, "generate.decode_step", 11.2, 11.8, attrs={"step": 4})]
    off = 100.0
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", off + 9.9, off + 12.5), _ev("generate", off + 10, off + 12),
        _ev("generate.decode_step", off + 10.5, off + 11.0),
        _ev("generate.decode_step", off + 11.2, off + 11.8)])])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        _ev("%fusion.1 = f32[2]", off + 10.0, off + 10.6),
        _ev("%convert.2 = bf16[2]", off + 11.0, off + 11.3)])])
    return recs, NS(planes=[host, dev])


def test_idle_gaps_clock_and_gaps(idle_gaps, traced):
    recs, profile = traced
    off, spread = idle_gaps.clock_offset(profile, recs)
    assert off == pytest.approx(100.0) and spread == pytest.approx(0, abs=1e-6)
    gaps = idle_gaps.gaps_of(profile)
    assert [round(e - s, 6) for s, e in gaps] == [1.2, 0.4, 0.1]
    # the gap inside step 3: 0.09 s lowering, 0.2 s compile, 0.11 s else
    s, e = gaps[1]
    got = idle_gaps.split_gap(s - off, e - off, recs,
                              [("lowerings", 10.61, 10.7), ("compiles", 10.7, 10.9),
                               ("traces", 12.0, 12.1)])
    assert got["span"] == "generate.decode_step" and got["step"] == 3
    assert got["lowerings_ms"] == pytest.approx(90, abs=1e-3)
    assert got["compiles_ms"] == pytest.approx(200, abs=1e-3)
    assert got["traces_ms"] == 0
    assert got["no_compile_event_ms"] == pytest.approx(110, abs=1e-3)
    # before the first span: no span
    s, e = gaps[2]
    assert idle_gaps.split_gap(s - off, e - off, recs, [])["span"] == "no span"


def test_scope_ops_tally():
    scope_ops = _load("scope_ops")
    hlo = "\n".join([
        '%convert.4 = bf16[16,8]{1,0} convert(%p), metadata={op_name="jit(f)/weight_cast/x"}',
        '  ROOT %dot.1 = f32[4]{0} dot(%a, %b), metadata={op_name="jit(f)/attn/dot"}',
        '%t = (f32[2], f32[2]) tuple(%a, %b), metadata={op_name="jit(f)/ffn/tuple"}',
        '%c = f32[2]{0} copy(%a), metadata={op_name="jit(f)/copy"}',
        '%n = f32[2]{0} negate(%a)'])
    assert scope_ops.tally(hlo) == {"weight_cast": [1, 256], "attn": [1, 16],
                                    "ffn": [1, 0], "-": [1, 8]}


def test_scope_ops_decode_step_has_every_scope():
    scope_ops = _load("scope_ops")
    (_, prefill), (_, decode) = scope_ops.model_programs("olmo-1b", True, 2, 8)
    got = scope_ops.tally(decode.compile().as_text())
    assert {"embed", "weight_cast", "attn", "kv_update", "ffn", "final_norm",
            "head"} <= set(got)
    assert all(n > 0 for n, _ in got.values())
