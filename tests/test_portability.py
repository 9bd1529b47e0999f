"""Cross-backend portability: the gpu_sm target and its isolation.

Pins down the second-hardware-target contract — the modeled GPU
SystemGraph's structure, the gpu lowering config, bit-exact oracle replay
and tuned <= greedy off the tpu path, and (the load-bearing part) that
artifact/tuning/model cache keys can NEVER collide across targets.
"""
from __future__ import annotations

import importlib.util
import os

import pytest

from repro.compile import compile_conv, compile_gemm, compile_gru
from repro.compile.cache import (ArtifactCache, artifact_key,
                                 sysgraph_fingerprint)
from repro.core.approach import GreedyApproach
from repro.core import kernels_ir as K
from repro.core.sysgraph import (GPU_SMEM_BYTES, GPU_SMS_PER_CLUSTER,
                                 TARGET_ALIASES, TARGETS, gpu_sm,
                                 paper_accelerator, resolve_target, tpu_v5e)
from repro.search.model import model_key
from repro.search.space import tuning_key
from repro.search.tune import build_cases, tune_case
from repro.verify import verify_artifact_dict
from repro.verify.mutate import MUTATIONS

CLUSTER_SMEM = GPU_SMS_PER_CLUSTER * GPU_SMEM_BYTES


# --------------------------------------------------------------------------- #
# The modeled GPU SystemGraph
# --------------------------------------------------------------------------- #


def test_gpu_sm_structure():
    g = gpu_sm(8)
    assert g.family == "gpu"
    assert g.memories["host"].role == "host"
    assert g.memories["hbm0"].role == "global"
    smems = [m for m in g.memories.values() if m.role == "staging"]
    assert len(smems) == 8
    assert all(m.capacity == CLUSTER_SMEM for m in smems)
    assert len(g.computes) == 8
    for c in g.computes.values():
        assert c.matmul_tile == (256, 256, 32)


def test_gpu_sm_staging_budget_reads_shared_memory():
    # The scheduler's tile budget comes from the graph's staging tier, so
    # the gpu budget is cluster shared memory, not TPU VMEM.
    assert gpu_sm(8).staging_budget() == CLUSTER_SMEM // 3
    assert tpu_v5e(1).staging_budget() == (128 << 20) // 3
    assert gpu_sm(8).min_matmul_tile() == (256, 256, 32)
    assert tpu_v5e(1).min_matmul_tile() == (128, 128, 128)


def test_gpu_sm_nvlink_fabric_edges():
    # n > 1 gets cluster-to-cluster ring links; n == 1 has none.
    multi = gpu_sm(4)
    links = [e for e in multi.edges
             if e.src.startswith("smem") and e.dst.startswith("smem")]
    assert links, "expected NVLink-class smem<->smem edges for n_sms > 1"
    single = gpu_sm(1)
    assert not [e for e in single.edges
                if e.src.startswith("smem") and e.dst.startswith("smem")]


def test_target_registry_and_aliases():
    assert set(TARGETS) == {"tpu_v5e", "gpu_sm", "paper"}
    assert resolve_target("gpu").name == resolve_target("gpu_sm").name
    assert resolve_target("v5e").name == resolve_target("tpu_v5e").name
    assert TARGET_ALIASES["tpu"] == "tpu_v5e"
    with pytest.raises(KeyError):
        resolve_target("tpu_v9000")


# --------------------------------------------------------------------------- #
# Cross-target cache isolation (fingerprints and keys)
# --------------------------------------------------------------------------- #


def test_sysgraph_fingerprints_distinct_and_stable():
    fps = {sysgraph_fingerprint(g)
           for g in (tpu_v5e(1), gpu_sm(8), paper_accelerator(2))}
    assert len(fps) == 3
    assert sysgraph_fingerprint(gpu_sm(8)) == sysgraph_fingerprint(gpu_sm(8))
    assert (sysgraph_fingerprint(gpu_sm(8))
            != sysgraph_fingerprint(gpu_sm(4)))


def test_cache_keys_never_collide_across_targets():
    prog = K.matmul(256, 128, 192)
    tpu, gpu = tpu_v5e(1), gpu_sm(8)
    assert (artifact_key(prog, tpu, GreedyApproach())
            != artifact_key(prog, gpu, GreedyApproach()))
    assert tuning_key(prog, tpu) != tuning_key(prog, gpu)
    assert model_key("gemm", tpu) != model_key("gemm", gpu)


def test_tpu_warmed_artifact_cache_misses_under_gpu(tmp_path):
    cache = ArtifactCache(str(tmp_path / "arts.json"))
    art = compile_gemm(256, 128, 192, graph=tpu_v5e(1), cache=cache)
    assert art.key in set(cache.keys())
    gpu_key = artifact_key(K.matmul(256, 128, 192), gpu_sm(8),
                           GreedyApproach())
    assert gpu_key not in set(cache.keys())
    assert cache.lookup(gpu_key) is None


# --------------------------------------------------------------------------- #
# GPU compiles: lowering config, oracle replay, tuned <= greedy
# --------------------------------------------------------------------------- #


def test_gpu_gemm_lowering_config():
    art = compile_gemm(512, 256, 192, graph=gpu_sm(2), use_cache=False)
    low = art.to_dict()["lowering"]
    assert low["kind"] == "pallas_gpu_gemm"
    bm, bn, bk = low["block"]
    assert low["smem_bytes"] == 4 * (bm * bk + bk * bn + bm * bn)
    assert 0 < low["smem_bytes"] <= CLUSTER_SMEM
    assert all(x >= 1 for x in low["grid"])


def test_tpu_gemm_lowering_unchanged():
    art = compile_gemm(512, 256, 192, graph=tpu_v5e(1), use_cache=False)
    assert art.to_dict()["lowering"]["kind"] == "pallas_gemm"


def test_gpu_compiles_every_smoke_kernel():
    g = gpu_sm(2)
    arts = [compile_gemm(256, 128, 192, graph=g, use_cache=False),
            compile_gru(4, 32, graph=g, use_cache=False),
            compile_conv(graph=g, use_cache=False, batch=2, h=6, w=6,
                         kh=3, kw=3, cin=8, cout=8)]
    for art in arts:
        assert art.cost > 0
        assert not verify_artifact_dict(art.to_dict())


def test_gpu_tune_bit_exact_and_tuned_le_greedy():
    case = build_cases("gemm", limit=1)[0]
    rep = tune_case(case, gpu_sm(8), "hillclimb", 6, 0, "cost",
                    validate=True)
    assert rep.ok
    assert rep.tuned_cost <= rep.greedy_cost
    assert rep.validation is not None and rep.validation.exact


# --------------------------------------------------------------------------- #
# Verifier: the art.lowering-target rule and the gpu mutation classes
# --------------------------------------------------------------------------- #


def test_lowering_target_rule_fires_on_crossed_configs():
    base = {"key": "k", "cost": 1.0, "instrs": [],
            "graph_name": "tpu_v5e_x1",
            "lowering": {"kind": "pallas_gpu_gemm", "block": [8, 8, 8],
                         "grid": [1, 1, 1], "smem_bytes": 768}}
    assert any(d.rule == "art.lowering-target"
               for d in verify_artifact_dict(base))
    crossed = dict(base, graph_name="gpu_sm_x8",
                   lowering={"kind": "pallas_gemm", "block": [8, 8, 8],
                             "grid": [1, 1, 1]})
    assert any(d.rule == "art.lowering-target"
               for d in verify_artifact_dict(crossed))
    missing_smem = dict(base, graph_name="gpu_sm_x8",
                        lowering={"kind": "pallas_gpu_gemm",
                                  "block": [8, 8, 8], "grid": [1, 1, 1]})
    assert any(d.rule == "art.lowering-target"
               for d in verify_artifact_dict(missing_smem))


def test_gpu_mutation_classes_registered():
    # The parametrized harness in test_verify.py runs them; here we pin the
    # registry so the classes cannot silently vanish.
    assert MUTATIONS["gpu-smem-capacity"][0] == "sch.capacity"
    assert MUTATIONS["gpu-wrong-lowering"][0] == "art.lowering-target"


# --------------------------------------------------------------------------- #
# The perf gate keys per target
# --------------------------------------------------------------------------- #


def _load_bench_run():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "run.py")
    spec = importlib.util.spec_from_file_location("bench_run", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_baseline_comparison_keys_rows_by_target():
    run = _load_bench_run()
    baseline = {"rows": [
        {"suite": "portability", "name": "port_gemm", "us_per_call": 10.0,
         "target": "tpu_v5e"},
        {"suite": "portability", "name": "port_gemm", "us_per_call": 50.0,
         "target": "gpu_sm"},
    ]}
    # Same name, per-target numbers: each row gates against its own target.
    records = [
        {"suite": "portability", "name": "port_gemm", "us_per_call": 10.0,
         "target": "tpu_v5e"},
        {"suite": "portability", "name": "port_gemm", "us_per_call": 50.0,
         "target": "gpu_sm"},
    ]
    assert run.compare_to_baseline(records, baseline, 5.0) == []
    # A gpu row must never satisfy (or be gated by) the tpu baseline: drop
    # the tpu record and the tpu baseline row reports missing even though a
    # same-named gpu row exists.
    violations = run.compare_to_baseline(records[1:], baseline, 5.0)
    assert len(violations) == 1
    assert "port_gemm@tpu_v5e" in violations[0]
    assert "missing" in violations[0]
    # And a slow gpu row is caught under its own target label.
    slow = [dict(records[0]),
            dict(records[1], us_per_call=80.0)]
    violations = run.compare_to_baseline(slow, baseline, 5.0)
    assert len(violations) == 1
    assert "port_gemm@gpu_sm" in violations[0]
