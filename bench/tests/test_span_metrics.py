"""CPU tests of the metric readers of the program's own spans
(``bench/metrics/``, through ``bench/span_readers.py``): on records built
from spans with set times and counters, on a program without spans (the
readers find nothing), and in traced runs of the smoke cells.  Run from the repository root:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_span_metrics.py
"""
from __future__ import annotations

import sys
import time
from types import SimpleNamespace

import jax
import pytest

from bench import harness, run_cell
from bench.tests.smoke import CPU, smoke_copy
from repro.runtime import spans

SPAN_METRICS = ("decode_host_ms", "decode_lowerings_per_step", "prefill_host_ms",
                "prefill_lowerings_per_call", "isam_schedule_s")


@pytest.fixture(autouse=True, scope="module")
def no_persistent_cache():
    """CPU programs loaded back from JAX's persistent cache can crash this
    host (AOT results for another CPU); these tests compile afresh."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def read(name, record):
    return harness.load_module("metrics", name).read(record)


def fake_span(name, start, end, lowerings=0, **attrs):
    """A recorded span named ``name`` from ``start`` to ``end``, with
    ``lowerings`` counted into it."""
    with spans.span(name, **attrs) as s:
        if lowerings:
            s.counters["lowerings"] = lowerings
    s.start, s.end = start, end
    return s


def fake_generate(t, prefill_s, decode_s, steps, prefill_lowerings, step_lowerings):
    """The spans of one ``generate`` call starting at ``t``; returns its end."""
    with spans.span("generate") as g:
        fake_span("generate.prefill", t, t + prefill_s, prefill_lowerings)
        with spans.span("generate.decode", steps=steps) as d:
            for i in range(steps):
                a = t + prefill_s + i * decode_s / steps
                fake_span("generate.decode_step", a, a + decode_s / steps,
                          step_lowerings, step=i + 1)
        d.start, d.end = t + prefill_s, t + prefill_s + decode_s
    g.start, g.end = t, t + prefill_s + decode_s
    return g.end


def test_decode_readers_on_fake_spans():
    t0 = time.perf_counter() + 1e3          # clear of every real span
    # before the window: the warm-up batch, which no reader may count
    fake_generate(t0 - 10, 0.5, 9.0, 3, 1, 5)
    due = t0
    end = fake_generate(t0 + 0.001, 0.1, 3.1, 31, 1, 1)
    end = fake_generate(end + 0.01, 0.1, 6.2, 31, 1, 3)
    r = SimpleNamespace(counts={"units": [{"due": due, "end": t0 + 0.5},
                                          {"due": t0 + 1, "end": end + 0.001}]})
    assert read("decode_host_ms", r) == pytest.approx(1e3 * 9.3 / 62)
    assert read("decode_lowerings_per_step", r) == pytest.approx((31 + 93) / 62)


def test_prefill_readers_on_fake_spans():
    t0 = time.perf_counter() + 2e3
    end = t0
    for prefill_s in (0.12, 0.2, 0.1):
        end = fake_generate(end + 0.01, prefill_s, 0.0, 0, 2, 0)
    r = SimpleNamespace(counts={"units": [{"due": t0, "end": end + 0.001}]})
    assert read("prefill_host_ms", r) == pytest.approx(120.0)
    assert read("prefill_lowerings_per_call", r) == pytest.approx(2.0)
    # no decode step: the decode readers find nothing to divide by
    assert read("decode_host_ms", r) is None
    assert read("decode_lowerings_per_step", r) is None


def test_readers_find_nothing_in_an_empty_window():
    r = SimpleNamespace(counts={"units": []})
    for name in SPAN_METRICS[:4]:
        assert read(name, r) is None


def test_isam_schedule_s_sums_the_schedule_spans_of_the_process():
    before = read("isam_schedule_s", None) or 0.0
    fake_span("isam.schedule", 0.0, 0.25)
    fake_span("isam.lower", 0.0, 1.0)
    assert read("isam_schedule_s", None) == pytest.approx(before + 0.25)


def test_readers_return_none_for_a_program_without_spans(monkeypatch):
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    t0 = time.perf_counter()
    r = SimpleNamespace(counts={"units": [{"due": t0 - 1e4, "end": t0 + 1e4}]})
    for name in SPAN_METRICS:
        assert read(name, r) is None


@pytest.fixture(scope="module")
def smoke(tmp_path_factory):
    return smoke_copy(tmp_path_factory.mktemp("smoke"))


@pytest.mark.parametrize("cell, metrics", [
    ("olmo-smoke.decode", ("decode_host_ms", "decode_lowerings_per_step")),
    ("qwen-smoke.prefill", ("prefill_host_ms", "prefill_lowerings_per_call")),
    ("gemm-smoke.bf16", ("isam_schedule_s",)),
])
def test_traced_smoke_run_reports_span_metrics(smoke, cell, metrics):
    """A traced run of each smoke cell reports its span metrics (a CPU trace
    has no device plane, so the reduction is skipped)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run_cell.Tracer, "summary", lambda self, attribute: None)
        r = run_cell.run(["--workload", cell, "--seed", "3000000001", "--seconds",
                          "2", "--trace", "1"], bench_dir=smoke, **CPU)
    assert r["correct"], r["checks"]
    values = {m: r["metrics"][m]["value"] for m in metrics}
    assert all(v > 0 for v in values.values()), values
    if cell == "olmo-smoke.decode":
        # the eager decode step lowers its scan on every call
        assert values["decode_lowerings_per_step"] >= 1
