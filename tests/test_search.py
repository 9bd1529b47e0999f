"""repro.search tests: the baseline anchors to GreedyApproach exactly,
search is deterministic under a fixed seed, tuned schedules never model
worse than greedy, the persistent cache round-trips, and winning schedules
replay bit-exact against the ISAMIR oracle through the executor."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.compile.cache import program_fingerprint, sysgraph_fingerprint
from repro.core import instructions as I
from repro.core import kernels_ir as K
from repro.core.approach import GreedyApproach
from repro.core.isel import select_instructions
from repro.core.scheduler import schedule
from repro.core.sysgraph import paper_accelerator, tpu_v5e
from repro.search.cache import (TuningCache, TuningRecord, lookup_gemm,
                                set_default_cache)
from repro.search.evaluate import CostModelEvaluator, validate_selection
from repro.search.space import ParamApproach, SearchSpace, config_key, tuning_key
from repro.search.strategies import STRATEGIES, hill_climb

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GEMM = (256, 192, 130)      # fixed case: odd k exercises boundary tiles


def _gemm_fixture(graph=None):
    graph = graph or tpu_v5e(1)
    prog = K.matmul(*GEMM)
    sel = select_instructions(prog, [I.mxu_matmul()], allow_transforms=False)
    return prog, sel, graph


# --------------------------------------------------------------------------- #
# space / ParamApproach
# --------------------------------------------------------------------------- #


def test_param_baseline_matches_greedy_exactly():
    prog, sel, graph = _gemm_fixture()
    space = SearchSpace.for_graph(graph)
    s_greedy = schedule(sel, graph, GreedyApproach())
    s_base = schedule(sel, graph, ParamApproach(space.baseline()))
    assert s_base.makespan == s_greedy.makespan
    assert [op.kind for op in s_base.ops] == [op.kind for op in s_greedy.ops]
    assert s_base.counts() == s_greedy.counts()


def test_param_approach_tolerates_unknown_config_values():
    """Records written by a newer version (unknown policy names, junk
    numerics) must degrade to the greedy defaults, not crash scheduling."""
    prog, sel, graph = _gemm_fixture()
    weird = {"unroll": "block_major", "device": "gpu_first", "source": "??",
             "vmem_frac": "lots", "tile_i": "wide"}
    s = schedule(sel, graph, ParamApproach(weird))
    s_greedy = schedule(sel, graph, GreedyApproach())
    assert s.makespan == s_greedy.makespan


def test_random_configs_schedule_and_stay_finite():
    prog, sel, graph = _gemm_fixture()
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(sel, graph)
    import random
    rng = random.Random(7)
    costs = [ev(space.random_config(rng)) for _ in range(5)]
    assert all(c > 0 for c in costs)
    assert any(np.isfinite(c) for c in costs)


def test_tile_guard_rejects_blowup():
    prog, sel, graph = _gemm_fixture()
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(sel, graph, max_tiles=1)
    assert ev(space.baseline()) == float("inf")


def test_fingerprints_structural():
    p1, p2 = K.matmul(64, 64, 64), K.matmul(64, 64, 64)
    p3 = K.matmul(64, 64, 128)
    assert program_fingerprint(p1) == program_fingerprint(p2)
    assert program_fingerprint(p1) != program_fingerprint(p3)
    g1, g2 = tpu_v5e(1), tpu_v5e(2)
    assert sysgraph_fingerprint(g1) == sysgraph_fingerprint(tpu_v5e(1))
    assert sysgraph_fingerprint(g1) != sysgraph_fingerprint(g2)
    assert tuning_key(p1, g1, "cost") != tuning_key(p1, g1, "measure")


# --------------------------------------------------------------------------- #
# strategies
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_strategy_deterministic_under_fixed_seed(name):
    prog, sel, graph = _gemm_fixture()
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(sel, graph)
    o1 = STRATEGIES[name](space, ev, trials=10, seed=5)
    o2 = STRATEGIES[name](space, ev, trials=10, seed=5)
    assert [(config_key(t.config), t.cost) for t in o1.trials] == \
           [(config_key(t.config), t.cost) for t in o2.trials]
    assert o1.best_config == o2.best_config
    assert o1.best_cost == o2.best_cost


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_tuned_cost_never_worse_than_greedy(name):
    """Every strategy evaluates the greedy-equivalent baseline first."""
    prog, sel, graph = _gemm_fixture()
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(sel, graph)
    greedy = schedule(sel, graph, GreedyApproach()).makespan
    o = STRATEGIES[name](space, ev, trials=8, seed=0)
    assert o.baseline_cost == greedy
    assert o.best_cost <= greedy
    assert o.trials[0].config == space.baseline()


def test_hill_climb_finds_improvement_on_deepbench_shape():
    graph = tpu_v5e(1)
    prog = K.matmul(1024, 128, 1024)
    sel = select_instructions(prog, [I.mxu_matmul()], allow_transforms=False)
    space = SearchSpace.for_graph(graph)
    o = hill_climb(space, CostModelEvaluator(sel, graph), trials=12, seed=0)
    assert o.best_cost < o.baseline_cost


# --------------------------------------------------------------------------- #
# cache
# --------------------------------------------------------------------------- #


def test_cache_roundtrip_same_schedule(tmp_path):
    """write -> fresh cache instance -> lookup -> identical schedule."""
    prog, sel, graph = _gemm_fixture()
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(sel, graph)
    o = hill_climb(space, ev, trials=6, seed=0)
    key = tuning_key(prog, graph, "cost")

    path = str(tmp_path / "tuning.json")
    TuningCache(path).store(TuningRecord(
        key=key, config=o.best_config, cost=o.best_cost,
        baseline_cost=o.baseline_cost, strategy="hillclimb", trials=6))

    rec = TuningCache(path).lookup(key)       # fresh instance, re-read disk
    assert rec is not None
    assert rec.config == o.best_config
    s1 = schedule(sel, graph, ParamApproach(o.best_config))
    s2 = schedule(sel, graph, ParamApproach(rec.config))
    assert s1.makespan == s2.makespan == rec.cost
    assert [op.kind for op in s1.ops] == [op.kind for op in s2.ops]


def test_round_robin_deterministic_on_reused_approach():
    """The round-robin cursor lives on the per-run scheduler state, so the
    same Approach instance yields the same schedule on repeated calls."""
    graph = paper_accelerator(2)
    prog = K.matmul(100, 80, 60)
    sel = select_instructions(prog, [I.mxu_matmul()], allow_transforms=False)
    app = ParamApproach({"device": "round_robin"})
    s1 = schedule(sel, graph, app)
    s2 = schedule(sel, graph, app)
    assert s1.makespan == s2.makespan
    assert [op.device for op in s1.ops] == [op.device for op in s2.ops]


def test_cache_save_merges_concurrent_writers(tmp_path):
    """Records stored by another process between our load and save must
    survive (merge-on-save, last writer wins per key not per file)."""
    path = str(tmp_path / "tuning.json")
    c1 = TuningCache(path)
    c1.store(TuningRecord(key="a", config={}, cost=1.0, baseline_cost=1.0))
    c2 = TuningCache(path)          # separate "process": own snapshot
    c2.load()
    c1.store(TuningRecord(key="b", config={}, cost=2.0, baseline_cost=2.0))
    c2.store(TuningRecord(key="c", config={}, cost=3.0, baseline_cost=3.0))
    final = TuningCache(path)
    assert sorted(final.keys()) == ["a", "b", "c"]


def test_cache_tolerates_corrupt_file(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text("{not json")
    c = TuningCache(str(path))
    assert len(c) == 0
    c.store(TuningRecord(key="k", config={}, cost=1.0, baseline_cost=1.0))
    assert TuningCache(str(path)).lookup("k") is not None


def test_lookup_gemm_prefers_measured(tmp_path):
    from repro.search.cache import gemm_tuning_key
    path = str(tmp_path / "tuning.json")
    c = TuningCache(path)
    c.store(TuningRecord(key=gemm_tuning_key(64, 64, 64, backend="cost"),
                         config={}, cost=2.0, baseline_cost=2.0,
                         backend="cost", tile=(128, 128, 128)), save=False)
    c.store(TuningRecord(key=gemm_tuning_key(64, 64, 64, backend="measure"),
                         config={}, cost=1.0, baseline_cost=2.0,
                         backend="measure", tile=(64, 64, 64)))
    set_default_cache(c)
    try:
        rec = lookup_gemm(64, 64, 64)
        assert rec is not None and rec.backend == "measure"
        assert lookup_gemm(65, 64, 64) is None
    finally:
        set_default_cache(None)


# --------------------------------------------------------------------------- #
# executor-vs-oracle validation
# --------------------------------------------------------------------------- #


def test_tuned_schedule_replays_bit_exact():
    prog, sel, graph = _gemm_fixture()
    space = SearchSpace.for_graph(graph)
    o = hill_climb(space, CostModelEvaluator(sel, graph), trials=8, seed=0)
    rep = validate_selection(prog, sel, graph, ParamApproach(o.best_config))
    assert rep.exact
    assert rep.max_abs_err == 0.0


def test_validation_multidevice_graph():
    graph = paper_accelerator(2)
    prog = K.gru_cell(4, 16, 12)
    sel = select_instructions(prog, I.tpu_isa())
    space = SearchSpace.for_graph(graph)
    o = STRATEGIES["evolve"](space, CostModelEvaluator(sel, graph),
                             trials=6, seed=2)
    rep = validate_selection(prog, sel, graph, ParamApproach(o.best_config))
    assert rep.ok       # f32-ulp summation grouping allowed for fused gates


# --------------------------------------------------------------------------- #
# CLI + benchmark harness smoke (subprocesses, as CI runs them)
# --------------------------------------------------------------------------- #


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return env


def test_tune_cli_smoke(tmp_path):
    cache = tmp_path / "cache.json"
    report = tmp_path / "report.json"
    res = subprocess.run(
        [sys.executable, "-m", "repro.search.tune", "--suite", "gemm",
         "--limit", "1", "--trials", "5", "--backend", "cost",
         "--cache", str(cache), "--json", str(report)],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    data = json.loads(cache.read_text())
    assert len(data["records"]) == 1
    rows = json.loads(report.read_text())["rows"]
    assert rows[0]["tuned_cost_s"] <= rows[0]["greedy_cost_s"]
    assert rows[0]["exact"] is True


def test_bench_run_unknown_suite_exits_2():
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "nosuch"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=120)
    assert res.returncode == 2
    assert "available" in res.stderr
    assert "mapper" in res.stderr


def test_bench_run_json_output(tmp_path):
    out = tmp_path / "BENCH_mapper.json"
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "mapper",
         "--json", str(out)],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    data = json.loads(out.read_text())
    assert data["failures"] == 0
    assert data["rows"] and all("suite" in r and "us_per_call" in r
                                for r in data["rows"])


def test_measure_backend_refuses_a_host_without_tpu():
    """Wall-clock tuning times the chip; on any other backend it raises
    instead of timing the Pallas interpreter."""
    from repro.search.evaluate import MeasuredGemmEvaluator
    with pytest.raises(RuntimeError, match="TPU"):
        MeasuredGemmEvaluator(64, 64, 64, tpu_v5e(1))


def test_measure_backend_refuses_parallel_workers(tmp_path, capsys):
    from repro.search.tune import main
    rc = main(["--suite", "gemm", "--limit", "1", "--backend", "measure",
               "--workers", "2", "--cache", str(tmp_path / "c.json")])
    assert rc == 2
    assert "--workers 1" in capsys.readouterr().err
