"""repro.compile tests: the pipeline is bit-identical to the historical
ad-hoc call chains, tiles are derived from mapping axis roles (not guessed
axis names), the artifact cache hits/misses on exactly the fingerprint
dimensions, and cached artifacts replay schedules that stay bit-exact
against the ISAMIR oracle."""
import json
import warnings

import pytest

from repro.compile import (ArtifactCache, CompileError, artifact_key,
                           compile_conv, compile_gemm,
                           compile_gru, compile_program, compile_selection,
                           gemm_selection, set_default_artifact_cache)
from repro.compile.cache import approach_fingerprint
from repro.compile.driver import clear_memo
from repro.core import instructions as I
from repro.core import kernels_ir as K
from repro.core.approach import GreedyApproach
from repro.core.isel import select_instructions
from repro.core.scheduler import schedule
from repro.core.sysgraph import paper_accelerator, tpu_v5e
from repro.fabric.simulate import compile_fabric
from repro.search.evaluate import validate_schedule
from repro.search.space import ParamApproach


@pytest.fixture(autouse=True)
def _isolate_caches():
    """No test leaks a process-default artifact cache or a stale memo."""
    clear_memo()
    set_default_artifact_cache(None)
    yield
    clear_memo()
    set_default_artifact_cache(None)


# --------------------------------------------------------------------------- #
# Pipeline equivalence with the historical call chains
# --------------------------------------------------------------------------- #


def test_compile_gemm_matches_legacy_chain():
    """Driver output == select_instructions + schedule, op for op."""
    m, n, k = 512, 192, 384
    prog = K.matmul(m, n, k)
    sel = select_instructions(prog, [I.mxu_matmul()], allow_transforms=False)
    legacy = schedule(sel, tpu_v5e(1), GreedyApproach())

    art = compile_gemm(m, n, k, use_cache=False)
    assert art.cost == legacy.makespan
    assert art.schedule.counts() == legacy.counts()
    assert [op.kind for op in art.schedule.ops] == \
        [op.kind for op in legacy.ops]


def test_gemm_tile_derived_from_axis_roles():
    art = compile_gemm(1024, 1024, 1024, use_cache=False)
    tile = art.gemm_tile()
    assert tile[0] == 128
    assert tile[1] % 128 == 0
    assert tile[2] >= 128
    assert art.lowering["kind"] == "pallas_gemm"
    assert tuple(art.lowering["block"]) == tile


def test_conv_extraction_tile_not_128_default():
    """The conv->matmul extraction renames haystack axes; role-derived tiles
    must reflect the real fused extents, not an i/j/k guess defaulting to
    128 (the historical _tile_from_schedule bug)."""
    art = compile_conv(use_cache=False, batch=2, h=6, w=6, kh=1, kw=1,
                       cin=8, cout=8)
    plan = art.instr_plan("mxu.matmul")
    hay_axes = {h for _, h in plan.axis_map}
    assert not {"i", "j", "k"} <= hay_axes      # axes really are renamed
    assert art.gemm_tile() == (72, 8, 8)        # fused extents, clamped


def test_unmappable_tile_request_raises():
    art = compile_gru(4, 16, use_cache=False)
    with pytest.raises(CompileError):
        art.instr_plan("mxu.matmul").tile_for("q")   # no such role
    with pytest.raises(CompileError):
        art.instr_plan("nonexistent.needle")


def test_compile_program_rejects_uncoverable():
    prog = K.matmul(64, 64, 64)
    with pytest.raises(CompileError):
        compile_program(prog, isa=[I.vpu_unary("exp")], use_cache=False)


def test_compile_selection_param_approach_matches_evaluator():
    from repro.search.evaluate import CostModelEvaluator
    from repro.search.space import SearchSpace
    prog, sel = gemm_selection(256, 192, 130)
    graph = tpu_v5e(1)
    space = SearchSpace.for_graph(graph)
    ev = CostModelEvaluator(sel, graph)
    cfg = space.baseline()
    art = compile_selection(sel, graph, ParamApproach(cfg))
    assert art.cost == ev(cfg)
    assert art.cost == schedule(sel, graph, GreedyApproach()).makespan


# --------------------------------------------------------------------------- #
# Artifact cache correctness (hit/miss dimensions + replay)
# --------------------------------------------------------------------------- #


def test_same_program_sysgraph_hits_cache(tmp_path):
    cache = ArtifactCache(str(tmp_path / "compiled.json"))
    a1 = compile_gemm(256, 128, 192, cache=cache)
    assert not a1.from_cache
    clear_memo()                       # force the persistent layer
    a2 = compile_gemm(256, 128, 192, cache=cache)
    assert a2.from_cache
    assert a2.key == a1.key
    assert a2.cost == a1.cost
    assert a2.gemm_tile() == a1.gemm_tile()
    assert a2.lowering == a1.lowering


def test_changed_sysgraph_misses(tmp_path):
    cache = ArtifactCache(str(tmp_path / "compiled.json"))
    a1 = compile_gemm(256, 128, 192, cache=cache)
    clear_memo()
    a2 = compile_gemm(256, 128, 192, graph=paper_accelerator(2), cache=cache)
    assert not a2.from_cache
    assert a2.key != a1.key


def test_changed_backend_and_approach_miss():
    prog = K.matmul(64, 64, 64)
    g = tpu_v5e(1)
    greedy = GreedyApproach()
    base = artifact_key(prog, g, greedy, "cost")
    assert artifact_key(prog, g, greedy, "measure") != base
    tuned = ParamApproach({"tile_i": 256})
    assert artifact_key(prog, g, tuned, "cost") != base


def test_changed_isa_or_transform_policy_misses():
    """Same program compiled under a different needle set (or transform
    policy) must not be served the other compile's artifact."""
    prog = K.gru_cell(4, 16, 16)
    full = compile_program(prog, isa=I.tpu_isa())
    unfused = compile_program(prog, isa=I.tpu_isa(include_fused=False))
    assert full.key != unfused.key
    full_needles = {p.needle for p in full.instrs}
    assert any(n.startswith("fused.") for n in full_needles)
    assert not any(p.needle.startswith("fused.") for p in unfused.instrs)
    g = tpu_v5e(1)
    mm = K.matmul(64, 64, 64)
    assert artifact_key(mm, g, GreedyApproach(), "cost",
                        [I.mxu_matmul()], True) != \
        artifact_key(mm, g, GreedyApproach(), "cost",
                     [I.mxu_matmul()], False)


def test_changed_jax_version_misses(tmp_path, monkeypatch):
    cache = ArtifactCache(str(tmp_path / "compiled.json"))
    compile_gemm(256, 128, 192, cache=cache)
    clear_memo()
    import repro.compile.cache as cache_mod
    monkeypatch.setattr(cache_mod, "jax_version", lambda: "99.0.0-test")
    a2 = compile_gemm(256, 128, 192, cache=cache)
    assert not a2.from_cache
    assert "jax=99.0.0-test" in a2.key


def test_opaque_approach_never_cached(tmp_path):
    class Wrapped(GreedyApproach):
        pass
    cache = ArtifactCache(str(tmp_path / "compiled.json"))
    assert approach_fingerprint(Wrapped()).startswith("opaque:")
    compile_gemm(64, 64, 64, approach=Wrapped(), cache=cache)
    assert len(cache) == 0


def test_cached_artifact_replays_bit_exact(tmp_path):
    """The satellite acceptance check: a cache-hydrated CompiledKernel
    rebuilds a schedule whose executor replay is bit-exact vs the oracle."""
    cache = ArtifactCache(str(tmp_path / "compiled.json"))
    m, n, k = 96, 80, 130                       # odd k: boundary tiles
    compile_gemm(m, n, k, cache=cache)
    clear_memo()
    art = compile_gemm(m, n, k, cache=cache)
    assert art.from_cache and art.schedule is None
    sched = art.ensure_schedule()
    prog = K.matmul(m, n, k)
    report = validate_schedule(prog, art.selection, sched)
    assert report.exact
    # and the replayed schedule reproduces the cached artifact's decisions
    assert sched.makespan == art.cost


def test_cached_gru_artifact_replays_bit_exact(tmp_path):
    cache = ArtifactCache(str(tmp_path / "compiled.json"))
    compile_gru(4, 16, cache=cache)
    clear_memo()
    art = compile_gru(4, 16, cache=cache)
    assert art.from_cache
    sched = art.ensure_schedule()
    report = validate_schedule(art.program, art.selection, sched)
    assert report.ok
    assert sched.makespan == art.cost


def test_cache_roundtrip_through_json(tmp_path):
    path = str(tmp_path / "compiled.json")
    a1 = compile_gemm(128, 64, 64, cache=ArtifactCache(path))
    raw = json.loads(open(path).read())
    assert raw["schema"] == 1 and len(raw["artifacts"]) == 1
    clear_memo()
    a2 = ArtifactCache(path).lookup(a1.key)
    assert a2 is not None
    assert a2.gemm_tile() == a1.gemm_tile()
    assert [p.needle for p in a2.instrs] == [p.needle for p in a1.instrs]


def test_corrupt_artifact_cache_warns_once(tmp_path):
    path = tmp_path / "compiled.json"
    path.write_text("{definitely not json")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        c = ArtifactCache(str(path))
        assert len(c) == 0
        ArtifactCache(str(path)).load()         # second reader: no new warn
    assert len([w for w in caught if "corrupt" in str(w.message)]) == 1
    # a corrupt cache degrades to empty, then heals on the next save
    art = compile_gemm(64, 64, 64, cache=c)
    clear_memo()
    assert ArtifactCache(str(path)).lookup(art.key) is not None


# --------------------------------------------------------------------------- #
# Entry-point consistency + fabric compiles
# --------------------------------------------------------------------------- #


def test_plan_gemm_and_plan_gru_route_through_driver():
    from repro.kernels import ops
    tile, secs = ops.plan_gemm(1024, 1024, 1024, use_cache=False)
    art = compile_gemm(1024, 1024, 1024, use_cache=False)
    assert tile == art.gemm_tile() and secs == art.cost
    (bb, bh), gsecs = ops.plan_gru(16, 64)
    assert (bb, bh) == (16, 64) and gsecs > 0


def test_compile_fabric_matches_simulator(tmp_path):
    from repro.fabric.partition import partition
    from repro.fabric.simulate import simulate_partition
    from repro.fabric.topology import make_topology
    topo = make_topology("ring", 2)
    shape = (256, 128, 192)
    art = compile_fabric("gemm", shape, topo, axis="k", use_cache=False)
    res = simulate_partition(partition("gemm", shape, "k", 2), topo,
                             None, "ring")
    assert art.cost == res.makespan
    assert art.fabric["axis"] == "k"
    assert art.fabric["collectives"] == [
        {"kind": "reduce_scatter", "buffer": "C", "when": "post", "axis": 0}]
    # fabric artifacts round-trip through the cache too
    cache = ArtifactCache(str(tmp_path / "compiled.json"))
    a1 = compile_fabric("gemm", shape, topo, axis="k", cache=cache)
    clear_memo()
    a2 = compile_fabric("gemm", shape, topo, axis="k", cache=cache)
    assert a2.from_cache and a2.cost == a1.cost
    assert a2.fabric == a1.fabric


def test_cached_fabric_artifact_replays_per_chip_schedule(tmp_path):
    """A cache-hydrated fabric artifact rebuilds chip 0's per-chip schedule
    (what a fresh compile attaches), not the unsharded program on the
    fabric graph."""
    from repro.fabric.topology import make_topology
    topo = make_topology("ring", 2)
    cache = ArtifactCache(str(tmp_path / "compiled.json"))
    fresh = compile_fabric("gemm", (256, 128, 192), topo, axis="k",
                           cache=cache)
    clear_memo()
    cached = compile_fabric("gemm", (256, 128, 192), topo, axis="k",
                            cache=cache)
    assert cached.from_cache
    sched = cached.ensure_schedule()
    assert sched.makespan == fresh.schedule.makespan
    assert sched.counts() == fresh.schedule.counts()


def test_dtype_table_single_source():
    from repro.core.dtypes import DTYPE_BYTES, dtype_bytes
    from repro.core import scheduler
    from repro.launch import hlo_analysis, hlo_flops
    assert scheduler.DTYPE_BYTES is DTYPE_BYTES
    assert hlo_flops._DTYPE_BYTES is DTYPE_BYTES
    assert hlo_analysis._DTYPE_BYTES is DTYPE_BYTES
    assert dtype_bytes("f32") == 4 and dtype_bytes("bf16") == 2
    assert dtype_bytes("no-such-dtype") == 4
