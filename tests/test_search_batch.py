"""Batched-evaluation tier tests: the vectorized guard and schedule keys
are bit-identical to the scalar path, every strategy produces the identical
winner and trial trace through ``evaluate_many``, incremental re-scheduling
reproduces from-scratch schedules op for op (and the verify layer catches
the two ways a delta resume can go wrong), and ``tune --workers`` merges a
bit-identical cache in deterministic case order."""
import json
import random

import pytest

from repro.compile.driver import (conv_selection, gemm_selection,
                                  gru_selection)
from repro.core.scheduler import (schedule, schedule_incremental,
                                  schedule_with_segments)
from repro.core.sysgraph import paper_accelerator, tpu_v5e
from repro.search.batch import BatchPlan, DeltaScheduler
from repro.search.evaluate import CostModelEvaluator
from repro.search.space import ParamApproach, SearchSpace, config_key
from repro.search.strategies import STRATEGIES
from repro.verify.schedule import verify_reschedule, verify_schedule

GEMM = (256, 192, 130)      # odd k exercises boundary tiles


def _sample_configs(space, n, seed=0):
    configs = list(space.enumerate_configs())
    idx = random.Random(seed).sample(range(len(configs)), n)
    return [configs[i] for i in idx]


# --------------------------------------------------------------------------- #
# Guard + schedule-key parity
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("sel_graph", [
    lambda: (gemm_selection(*GEMM)[1], tpu_v5e(1)),
    lambda: (gru_selection(4, 256, 64)[1], tpu_v5e(1)),
    lambda: (gemm_selection(512, 128, 512)[1], paper_accelerator(2)),
])
def test_batch_guard_matches_scalar(sel_graph):
    sel, graph = sel_graph()
    ev = CostModelEvaluator(sel, graph)
    space = SearchSpace.for_graph(graph)
    configs = _sample_configs(space, 64)
    feasible, keys = ev.plan.analyze(configs, ev.max_tiles)
    for cfg, ok in zip(configs, feasible):
        want = ev.estimated_tiles(ParamApproach(cfg)) <= ev.max_tiles
        assert bool(ok) == want, cfg
    assert len(keys) == len(configs)


def test_equal_keys_mean_equal_cost():
    _, sel = gemm_selection(*GEMM)
    graph = tpu_v5e(1)
    space = SearchSpace.for_graph(graph)
    configs = _sample_configs(space, 48)
    plan = BatchPlan(sel, graph)
    feasible, keys = plan.analyze(configs, 4096)
    by_key = {}
    for cfg, ok, key in zip(configs, feasible, keys):
        if not ok:
            continue
        cost = CostModelEvaluator(sel, graph, incremental=False)(cfg)
        by_key.setdefault(key, set()).add(cost)
    assert by_key, "no feasible config in sample"
    for key, costs in by_key.items():
        assert len(costs) == 1, f"key {key} scored {costs}"


def test_evaluate_many_bit_identical_to_scalar():
    _, sel = gemm_selection(*GEMM)
    graph = tpu_v5e(1)
    space = SearchSpace.for_graph(graph)
    configs = _sample_configs(space, 64)
    batch = CostModelEvaluator(sel, graph)
    scores = batch.evaluate_many(configs)
    scalar = CostModelEvaluator(sel, graph)
    assert scores == [scalar(c) for c in configs]
    assert batch.stats.evals == len(configs)
    assert batch.stats.memo_hits > 0       # 64 samples alias to far fewer keys


def test_unschedulable_selection_scores_inf():
    # On a graph where some instruction has no device, every config is inf
    # through both paths (compile would fail).
    _, sel = gru_selection(4, 64)
    graph = tpu_v5e(1)
    ev = CostModelEvaluator(sel, graph)
    ev.plan.unschedulable = True
    assert ev.evaluate_many([SearchSpace.for_graph(graph).baseline()]) \
        == [float("inf")]


# --------------------------------------------------------------------------- #
# Strategy equivalence: batched == sequential, every strategy
# --------------------------------------------------------------------------- #


CASES = {
    "gemm": lambda: (gemm_selection(*GEMM)[1], tpu_v5e(1)),
    "conv": lambda: (conv_selection(batch=2, h=8, w=8, kh=3, kw=3,
                                    cin=8, cout=8)[1], tpu_v5e(1)),
    "gemm_paper": lambda: (gemm_selection(256, 128, 256)[1],
                           paper_accelerator(2)),
}


def _trace(outcome):
    return [(config_key(t.config), t.cost) for t in outcome.trials]


@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
@pytest.mark.parametrize("case", sorted(CASES))
def test_strategy_trace_identical_batched_vs_scalar(strategy, case):
    sel, graph = CASES[case]()
    space = SearchSpace.for_graph(graph)
    kw = {}
    if strategy == "surrogate":
        # deterministic fake predictor: enough to drive the ranking phases
        kw = {"predict":
              lambda c: float(abs(hash(config_key(c))) % 997) / 997.0,
              "seeds": [space.baseline()]}
    batched = CostModelEvaluator(sel, graph)
    seq_ev = CostModelEvaluator(sel, graph)
    out_b = STRATEGIES[strategy](space, batched, trials=16, seed=7, **kw)
    out_s = STRATEGIES[strategy](space, lambda c: seq_ev(c),
                                 trials=16, seed=7, **kw)
    assert _trace(out_b) == _trace(out_s)
    assert config_key(out_b.best_config) == config_key(out_s.best_config)
    assert out_b.best_cost == out_s.best_cost


# --------------------------------------------------------------------------- #
# Incremental re-scheduling
# --------------------------------------------------------------------------- #


def _hetero_gru():
    """input dim != hidden dim: instruction 0's reduction (k=64) is below
    the hardware tile, hence cap-invariant — tile_k changes share its
    prefix and first_changed is 1."""
    _, sel = gru_selection(4, 256, 64)
    return sel, tpu_v5e(1)


def _ops_equal(a, b) -> bool:
    if len(a.ops) != len(b.ops):
        return False
    for x, y in zip(a.ops, b.ops):
        if (x.kind, x.device, x.src, x.dst, x.region, x.start, x.end) \
                != (y.kind, y.device, y.src, y.dst, y.region, y.start, y.end):
            return False
        tx, ty = x.tile, y.tile
        if (tx is None) != (ty is None):
            return False
        if tx is not None and (tx.instr_idx, tx.needle_name, tx.offsets,
                               tx.sizes, tx.device) \
                != (ty.instr_idx, ty.needle_name, ty.offsets, ty.sizes,
                    ty.device):
            return False
    return True


def test_incremental_reschedule_bit_exact():
    sel, graph = _hetero_gru()
    base = SearchSpace.for_graph(graph).baseline()
    parent, segments = schedule_with_segments(sel, graph, ParamApproach(base))
    assert _ops_equal(parent, schedule(sel, graph, ParamApproach(base)))
    for tk in (128, 256):
        child_ap = ParamApproach(dict(base, tile_k=tk))
        inc, _ = schedule_incremental(sel, graph, child_ap, parent,
                                      segments, 1)
        full = schedule(sel, graph, child_ap)
        assert inc.makespan == full.makespan
        assert _ops_equal(inc, full)
        assert inc.final_residency == full.final_residency
        assert verify_reschedule(inc, sel, child_ap, graph) == []


def test_incremental_fallback_without_usable_segment():
    # first_changed 0 (or a missing segment) must degrade to a full
    # from-scratch schedule, never a wrong one.
    sel, graph = _hetero_gru()
    base = SearchSpace.for_graph(graph).baseline()
    ap = ParamApproach(base)
    parent, segments = schedule_with_segments(sel, graph, ap)
    sched, _ = schedule_incremental(sel, graph, ap, parent, {}, 5)
    assert _ops_equal(sched, parent)
    sched0, _ = schedule_incremental(sel, graph, ap, parent, segments, 0)
    assert _ops_equal(sched0, parent)


def test_delta_scheduler_fires_and_matches():
    sel, graph = _hetero_gru()
    space = SearchSpace.for_graph(graph)
    base = space.baseline()
    sweep = [dict(base, tile_k=tk, vmem_frac=vf)
             for tk in (None, 128, 256, 512) for vf in (1.0, 0.5)]
    ev = CostModelEvaluator(sel, graph)
    scores = ev.evaluate_many(sweep)
    assert ev.stats.delta > 0, "incremental path never fired"
    check = CostModelEvaluator(sel, graph, incremental=False)
    assert scores == check.evaluate_many(sweep)


def test_delta_scheduler_respects_policy_suffix():
    # An anchor with a different unroll/device/source policy must never be
    # resumed from — keys carry the policy suffix and DeltaScheduler only
    # matches same-policy anchors.
    sel, graph = _hetero_gru()
    base = SearchSpace.for_graph(graph).baseline()
    plan = BatchPlan(sel, graph)
    delta = DeltaScheduler(sel, graph)
    cfg_a = dict(base)
    cfg_b = dict(base, tile_k=128, unroll="red_major")
    _, (key_a, key_b) = plan.analyze([cfg_a, cfg_b], 4096)
    delta.schedule_for(ParamApproach(cfg_a), key_a)
    sched = delta.schedule_for(ParamApproach(cfg_b), key_b)
    assert delta.stats == {"fresh": 2, "delta": 0}
    full = schedule(sel, graph, ParamApproach(cfg_b))
    assert _ops_equal(sched, full)


# --------------------------------------------------------------------------- #
# Verify layer: the two incremental corruption classes
# --------------------------------------------------------------------------- #


def test_stale_stream_is_replay_silent_but_caught():
    from repro.verify.mutate import _incremental_bundle
    b = _incremental_bundle()
    bad, _ = schedule_incremental(b.selection, b.sysgraph, b.approach,
                                  b.parent_schedule, b.segments,
                                  b.first_changed + 1)
    # self-consistent splice: the replay rules all stay silent...
    assert verify_schedule(bad, b.approach) == []
    # ...but the tile recomputation flags the stale instruction
    diags = verify_reschedule(bad, b.selection, b.approach, b.sysgraph)
    assert [d.rule for d in diags] == ["sch.tile-mismatch"]


def test_incremental_mutations_caught():
    from repro.verify.mutate import run_mutation
    for name in ("inc-stale-stream", "inc-wrong-instr"):
        res = run_mutation(name)
        assert res.caught, str(res)


def test_incremental_bundle_baseline_clean():
    from repro.verify.mutate import _incremental_bundle, _verify_bundle
    assert _verify_bundle(_incremental_bundle()) == []


# --------------------------------------------------------------------------- #
# Parallel tuning: deterministic shared-cache merge
# --------------------------------------------------------------------------- #


def test_tune_workers_deterministic(tmp_path):
    from repro.search.tune import main
    seq_cache = tmp_path / "seq.json"
    par_cache = tmp_path / "par.json"
    seq_json = tmp_path / "seq_rep.json"
    par_json = tmp_path / "par_rep.json"
    common = ["--suite", "gemm", "--limit", "2", "--trials", "8",
              "--no-validate"]
    assert main(common + ["--cache", str(seq_cache),
                          "--json", str(seq_json)]) == 0
    assert main(common + ["--cache", str(par_cache),
                          "--json", str(par_json), "--workers", "2"]) == 0
    assert json.loads(seq_cache.read_text()) \
        == json.loads(par_cache.read_text())

    def rows(path):
        return [{k: v for k, v in r.items()
                 if k not in ("elapsed_s", "counters")}
                for r in json.loads(path.read_text())["rows"]]
    assert rows(seq_json) == rows(par_json)


def test_tune_json_reports_throughput_counters(tmp_path):
    from repro.search.tune import main
    out = tmp_path / "rep.json"
    assert main(["--suite", "gemm", "--limit", "1", "--trials", "8",
                 "--no-validate", "--cache", str(tmp_path / "c.json"),
                 "--json", str(out)]) == 0
    row = json.loads(out.read_text())["rows"][0]
    counters = row["counters"]
    for field in ("evals", "guard_rejects", "memo_hits", "fresh", "delta",
                  "schedule_s", "predict_s", "configs_per_sec"):
        assert field in counters, field
    assert counters["evals"] > 0
    assert counters["configs_per_sec"] > 0


def test_file_lock_serializes_concurrent_saves(tmp_path):
    # Two stores saving "concurrently" (interleaved in one process) must
    # both survive: the lock serializes the merge-on-save read-modify-write.
    from repro.search.cache import TuningCache, TuningRecord
    path = str(tmp_path / "cache.json")
    a, b = TuningCache(path), TuningCache(path)
    a.store(TuningRecord(key="ka", config={}, cost=1.0, baseline_cost=1.0),
            save=False)
    b.store(TuningRecord(key="kb", config={}, cost=2.0, baseline_cost=2.0),
            save=False)
    a.save()
    b.save()
    assert set(TuningCache(path).load()) == {"ka", "kb"}
