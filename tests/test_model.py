"""Learned-cost-model tests: feature extraction is deterministic across
program families, seeded ridge training reproduces bit-identical predictions,
model artifacts survive a JSON round-trip, surrogate-guided search keeps the
baseline anchor (never worse than greedy) and degrades gracefully to the
cost backend when no/insufficient training data exists, and the benchmark
perf gate catches regressions, missing rows, and vacuous (empty) suites."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import kernels_ir as K
from repro.core.approach import GreedyApproach
from repro.core.scheduler import schedule
from repro.core.sysgraph import paper_accelerator, tpu_v5e
from repro.search.cache import TuningCache, TuningRecord
from repro.search.evaluate import CostModelEvaluator, LearnedEvaluator
from repro.search.features import (feature_dict, feature_names,
                                   feature_vector, program_family,
                                   role_extents)
from repro.search.model import MIN_TRAIN_SAMPLES, ModelStore, fresh_labels, harvest_cache, model_key, predict_gemm_block, train_family, train_suites
from repro.search.space import SearchSpace, tuning_key
from repro.search.strategies import hill_climb, surrogate_search
from repro.search.tune import build_cases, tune_case

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _case(name_prefix="gemm_256x192x130"):
    for c in build_cases("gemm") + build_cases("conv"):
        if c.name.startswith(name_prefix):
            return c
    raise AssertionError(name_prefix)


def _small_gemm_case():
    from repro.compile import gemm_selection
    from repro.search.tune import TuneCase
    prog, sel = gemm_selection(256, 192, 130)
    return TuneCase("gemm_256x192x130", prog, sel, prog, prog, sel,
                    gemm_shape=(256, 192, 130))


# --------------------------------------------------------------------------- #
# feature extraction
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("prog", [
    K.matmul(256, 192, 130),
    K.gru_cell(8, 64, 64),
    K.conv2d(2, 8, 8, 3, 3, 8, 16),
])
def test_features_finite_and_deterministic(prog):
    graph = tpu_v5e(1)
    cfg = {"tile_i": 256, "tile_k": None, "unroll": "red_major",
           "vmem_frac": 0.5}
    d1 = feature_dict(cfg, prog, graph)
    d2 = feature_dict(cfg, prog, graph)
    assert d1 == d2
    assert all(np.isfinite(v) for v in d1.values())
    names = feature_names(prog, graph)
    assert names == tuple(d1)           # stable ordering = model schema
    v = feature_vector(cfg, prog, graph, names)
    assert v.shape == (len(names),)


def test_feature_names_identical_across_programs_and_graphs():
    """One schema for every family/machine — family models share code."""
    n1 = feature_names(K.matmul(64, 64, 64), tpu_v5e(1))
    n2 = feature_names(K.gru_cell(4, 16, 16), paper_accelerator(2))
    assert n1 == n2


def test_program_family_strips_shapes():
    assert program_family(K.matmul(64, 64, 64)) == "matmul"
    assert program_family(K.matmul(128, 256, 512)) == "matmul"
    assert program_family("gru_cell_16x256") == "gru_cell"
    assert program_family("conv2d") == "conv2d"


def test_role_extents_from_conv_selection():
    """Conv extractions map MXU roles onto fused axes; the role extents must
    come from the mapping, not from axis-name guessing."""
    case = _case("conv3x3")
    roles = role_extents(case.selection)
    assert set(roles) == {"i", "j", "k"}
    assert all(v > 0 for v in roles.values())
    # tile-cap features must bind against those extents
    d_free = feature_dict({"tile_j": 4096}, case.program, tpu_v5e(1),
                          roles=roles)
    d_bind = feature_dict({"tile_j": 128}, case.program, tpu_v5e(1),
                          roles={**roles, "j": 4096})
    assert d_free["tile_j_binds"] == 0.0
    assert d_bind["tile_j_binds"] == 1.0
    assert d_bind["tile_j_excess"] > 0.0


def test_config_features_tolerate_junk_configs():
    d = feature_dict({"tile_i": "wide", "unroll": "nope", "vmem_frac": "x"},
                     K.matmul(64, 64, 64), tpu_v5e(1))
    base = feature_dict({}, K.matmul(64, 64, 64), tpu_v5e(1))
    assert d == base                    # degrades exactly like ParamApproach


# --------------------------------------------------------------------------- #
# training: determinism, round-trip, insufficient data
# --------------------------------------------------------------------------- #


def _labeled_samples(n=32, seed=0):
    case = _small_gemm_case()
    return case, fresh_labels(case, tpu_v5e(1), n=n, seed=seed)


def test_fresh_labels_deterministic():
    _, s1 = _labeled_samples(24, seed=3)
    _, s2 = _labeled_samples(24, seed=3)
    assert [(sorted(s.config.items()), s.cost) for s in s1] == \
           [(sorted(s.config.items()), s.cost) for s in s2]


def test_train_predict_deterministic():
    case, samples = _labeled_samples(32)
    graph = tpu_v5e(1)
    key = model_key("matmul", graph)
    m1, met1 = train_family(key, "matmul", samples, graph, seed=7)
    m2, met2 = train_family(key, "matmul", samples, graph, seed=7)
    assert m1 is not None
    assert np.array_equal(m1.weights, m2.weights)
    assert met1 == met2
    cfg = {"tile_i": 256}
    assert m1.predict(cfg, case.program, graph) == \
        m2.predict(cfg, case.program, graph)


def test_model_json_roundtrip(tmp_path):
    case, samples = _labeled_samples(32)
    graph = tpu_v5e(1)
    model, _ = train_family(model_key("matmul", graph), "matmul", samples,
                            graph)
    path = str(tmp_path / "models.json")
    ModelStore(path).store(model)

    loaded = ModelStore(path).lookup(model.key)   # fresh instance, re-read
    assert loaded is not None
    assert loaded.names == model.names
    space = SearchSpace.for_graph(graph)
    import random
    rng = random.Random(0)
    for _ in range(10):
        cfg = space.random_config(rng)
        assert loaded.predict(cfg, case.program, graph) == pytest.approx(
            model.predict(cfg, case.program, graph), rel=0, abs=0)


def test_train_refuses_insufficient_samples():
    case, samples = _labeled_samples(8)
    graph = tpu_v5e(1)
    model, metrics = train_family(
        model_key("matmul", graph), "matmul",
        samples[:MIN_TRAIN_SAMPLES - 1], graph)
    assert model is None
    assert metrics["trained"] is False
    assert "required" in metrics["reason"]


def test_store_skips_schema_drifted_models(tmp_path):
    case, samples = _labeled_samples(32)
    graph = tpu_v5e(1)
    model, _ = train_family(model_key("matmul", graph), "matmul", samples,
                            graph)
    path = str(tmp_path / "models.json")
    store = ModelStore(path)
    store.store(model)
    raw = json.loads(open(path).read())
    raw["models"][0]["feature_schema"] = 999
    open(path, "w").write(json.dumps(raw))
    assert ModelStore(path).lookup(model.key) is None   # drift => no model


def test_harvest_cache_yields_winner_and_baseline(tmp_path):
    case = _small_gemm_case()
    graph = tpu_v5e(1)
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(case.selection, graph)
    o = hill_climb(space, ev, trials=8, seed=0)
    cache = TuningCache(str(tmp_path / "t.json"))
    cache.store(TuningRecord(
        key=tuning_key(case.program, graph, "cost"), config=o.best_config,
        cost=o.best_cost, baseline_cost=o.baseline_cost))
    samples = harvest_cache(cache, [case], graph)
    assert len(samples) == 2
    assert all(s.source == "cache" for s in samples)
    assert {s.cost for s in samples} == {o.best_cost, o.baseline_cost}


# --------------------------------------------------------------------------- #
# surrogate search: anchoring + fallback
# --------------------------------------------------------------------------- #


def _trained_evaluator(case, graph, tmp_path):
    samples = fresh_labels(case, graph, n=40, seed=0)
    model, _ = train_family(
        model_key(program_family(case.program), graph),
        program_family(case.program), samples, graph)
    store = ModelStore(str(tmp_path / "m.json"))
    store.store(model)
    return LearnedEvaluator.for_selection(case.selection, graph, store=store)


def test_surrogate_never_worse_than_greedy(tmp_path):
    case = _small_gemm_case()
    graph = tpu_v5e(1)
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(case.selection, graph)
    greedy = schedule(case.selection, graph, GreedyApproach()).makespan
    le = _trained_evaluator(case, graph, tmp_path)
    o = surrogate_search(space, ev, trials=10, seed=0,
                         predict=le.predictor)
    assert o.trials[0].config == space.baseline()   # baseline-first
    assert o.baseline_cost == greedy
    assert o.best_cost <= greedy
    assert o.strategy == "surrogate"


def test_surrogate_deterministic_under_fixed_seed(tmp_path):
    case = _small_gemm_case()
    graph = tpu_v5e(1)
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(case.selection, graph)
    le = _trained_evaluator(case, graph, tmp_path)
    o1 = surrogate_search(space, ev, trials=12, seed=5, predict=le.predictor)
    o2 = surrogate_search(space, ev, trials=12, seed=5, predict=le.predictor)
    assert [(sorted(t.config.items()), t.cost) for t in o1.trials] == \
           [(sorted(t.config.items()), t.cost) for t in o2.trials]


def test_surrogate_matches_hillclimb_at_half_budget(tmp_path):
    """The acceptance property at test scale: trained + anchored surrogate
    reaches hillclimb's best with half the real evaluations."""
    case = _small_gemm_case()
    graph = tpu_v5e(1)
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(case.selection, graph)
    hc = hill_climb(space, ev, trials=16, seed=0)

    cache = TuningCache(str(tmp_path / "t.json"))
    cache.store(TuningRecord(
        key=tuning_key(case.program, graph, "cost"), config=hc.best_config,
        cost=hc.best_cost, baseline_cost=hc.baseline_cost))
    samples = harvest_cache(cache, [case], graph)
    samples += fresh_labels(case, graph, n=40, seed=0,
                            anchors=[hc.best_config])
    model, _ = train_family(model_key("matmul", graph), "matmul", samples,
                            graph)
    sg = surrogate_search(space, ev, trials=8, seed=0,
                          predict=model.predictor(case.program, graph),
                          seeds=list(model.meta["anchors"]) or
                          [hc.best_config])
    assert sg.best_cost <= hc.best_cost
    assert sg.evaluations <= hc.evaluations // 2


def test_surrogate_without_model_falls_back_to_hillclimb():
    case = _small_gemm_case()
    graph = tpu_v5e(1)
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(case.selection, graph)
    o = surrogate_search(space, ev, trials=10, seed=0, predict=None)
    hc = hill_climb(space, ev, trials=10, seed=0)
    assert o.strategy == "surrogate:fallback-hillclimb"
    assert o.best_cost == hc.best_cost
    assert [t.cost for t in o.trials] == [t.cost for t in hc.trials]


def test_learned_evaluator_none_without_store_or_model(tmp_path):
    case = _small_gemm_case()
    graph = tpu_v5e(1)
    assert LearnedEvaluator.for_selection(case.selection, graph,
                                          store=None) is None
    empty = ModelStore(str(tmp_path / "empty.json"))
    assert LearnedEvaluator.for_selection(case.selection, graph,
                                          store=empty) is None


def test_tune_case_learned_backend_degrades_to_cost(tmp_path):
    """--backend learned with no trained model must behave exactly like the
    cost backend (and still satisfy tuned <= greedy)."""
    case = _small_gemm_case()
    graph = tpu_v5e(1)
    rep = tune_case(case, graph, "hillclimb", 6, 0, "learned",
                    validate=False,
                    model_store=ModelStore(str(tmp_path / "none.json")))
    assert rep.backend == "cost"
    assert rep.tuned_cost <= rep.greedy_cost


def test_train_suites_trains_and_stores(tmp_path):
    graph = tpu_v5e(1)
    cache = TuningCache(str(tmp_path / "t.json"))     # empty: fresh-only
    store = ModelStore(str(tmp_path / "m.json"))
    rows = train_suites("conv", graph, cache, store, samples_per_case=20,
                        seed=0)
    trained = [r for r in rows if r["trained"]]
    assert trained
    assert all("train_mae_log" in r for r in trained)
    assert len(store) == len(trained)


# --------------------------------------------------------------------------- #
# learned GEMM block prediction
# --------------------------------------------------------------------------- #


def test_predict_gemm_block_requires_store(tmp_path):
    assert predict_gemm_block(64, 64, 64, store=None) is None


# --------------------------------------------------------------------------- #
# benchmark perf gate (compare mode + empty-suite behavior)
# --------------------------------------------------------------------------- #


def _bench_run_module():
    spec = importlib.util.spec_from_file_location(
        "bench_run_for_test", os.path.join(ROOT, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_compare_to_baseline_detects_regressions_and_missing():
    mod = _bench_run_module()
    baseline = {"rows": [
        {"suite": "s", "name": "a", "us_per_call": 100.0},
        {"suite": "s", "name": "gone", "us_per_call": 10.0},
        {"suite": "s", "name": "err", "us_per_call": -1.0},
    ]}
    records = [
        {"suite": "s", "name": "a", "us_per_call": 109.0},
        {"suite": "s", "name": "err", "us_per_call": -1.0,
         "error": "still broken"},
        {"suite": "s", "name": "new", "us_per_call": 1.0},
    ]
    v = mod.compare_to_baseline(records, baseline, tolerance_pct=5.0)
    assert len(v) == 2
    assert any("gone" in x and "missing" in x for x in v)
    assert any("a" in x and "exceeds" in x for x in v)
    # within tolerance: no violations
    ok = mod.compare_to_baseline(
        [{"suite": "s", "name": "a", "us_per_call": 104.0},
         {"suite": "s", "name": "gone", "us_per_call": 10.0},
         {"suite": "s", "name": "err", "us_per_call": -1.0}],
        baseline, tolerance_pct=5.0)
    assert ok == []


def test_compare_flags_newly_erroring_row():
    mod = _bench_run_module()
    baseline = {"rows": [{"suite": "s", "name": "a", "us_per_call": 5.0}]}
    v = mod.compare_to_baseline(
        [{"suite": "s", "name": "a", "us_per_call": -1.0, "error": "boom"}],
        baseline, tolerance_pct=5.0)
    assert len(v) == 1 and "now errors" in v[0]


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


def test_bench_run_empty_suite_fails(tmp_path, monkeypatch):
    """A suite emitting zero rows must exit non-zero (the gate can't green
    on vacuous output)."""
    stub = tmp_path / "benchmarks"
    stub.mkdir()
    (stub / "bench_mapper.py").write_text("def run():\n    return []\n")
    run_py = open(os.path.join(ROOT, "benchmarks", "run.py")).read()
    (stub / "run.py").write_text(run_py)
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "mapper"],
        cwd=str(tmp_path), env=_env(), capture_output=True, text=True,
        timeout=120)
    assert res.returncode == 1
    assert "emitted no rows" in res.stderr


def test_committed_ci_baseline_is_valid():
    """The committed perf baseline must parse and carry gateable rows from
    the deterministic modeled-cost suites."""
    path = os.path.join(ROOT, "benchmarks", "baselines", "BENCH_ci.json")
    data = json.load(open(path))
    assert data["failures"] == 0
    suites = {r["suite"] for r in data["rows"]}
    assert suites == {"tuned", "fabric", "graph", "serve", "search",
                      "portability"}
    assert all(r["us_per_call"] > 0 for r in data["rows"])
    # Multi-target rows must carry their target label — the gate keys on
    # (suite, name, target) so backends never gate against each other.
    port = [r for r in data["rows"] if r["suite"] == "portability"]
    assert port and {r.get("target") for r in port} == {"tpu_v5e", "gpu_sm"}


# --------------------------------------------------------------------------- #
# CLI smoke (subprocess, as CI runs it)
# --------------------------------------------------------------------------- #


def test_model_cli_train_eval_roundtrip(tmp_path):
    cache = tmp_path / "cache.json"
    store = tmp_path / "models.json"
    report = tmp_path / "train.json"
    res = subprocess.run(
        [sys.executable, "-m", "repro.search.tune", "--suite", "gemm",
         "--limit", "1", "--trials", "6", "--cache", str(cache),
         "--no-validate"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    res = subprocess.run(
        [sys.executable, "-m", "repro.search.model", "train", "--suite",
         "gemm", "--cache", str(cache), "--store", str(store),
         "--samples", "20", "--json", str(report)],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    rows = json.loads(report.read_text())["rows"]
    assert any(r["trained"] for r in rows)
    assert json.loads(store.read_text())["models"]
    res = subprocess.run(
        [sys.executable, "-m", "repro.search.tune", "--suite", "gemm",
         "--limit", "1", "--trials", "4", "--backend", "learned",
         "--model", str(store), "--cache", str(tmp_path / "c2.json"),
         "--no-validate", "--json", str(tmp_path / "r2.json")],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    row = json.loads((tmp_path / "r2.json").read_text())["rows"][0]
    assert row["strategy"] == "surrogate"
    assert row["tuned_cost_s"] <= row["greedy_cost_s"]
