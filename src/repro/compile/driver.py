"""The compilation driver: one entry point in front of the whole pipeline.

``compile_program`` is the general entry (any ISAMIR program, any system
graph, any Approach); ``compile_gemm`` / ``compile_gru`` / ``compile_conv``
are the workload frontends the kernels, the tuner and the benchmarks share;
``compile_selection`` runs the back half of the pipeline when an instruction
selection is already in hand (the search evaluators and per-chip fabric
compiles).  ``lookup_artifact`` / ``store_artifact`` are the one cache policy
every entry shares, ``repro.fabric``'s multi-chip compile included.

Every entry produces (or replays) a ``CompiledKernel``.  Fresh compiles are
memoized in-process per artifact key; the persistent artifact cache is
consulted when one is passed explicitly or activated process-wide
(``repro.compile.cache.set_default_artifact_cache`` — the CLI does this).
"""
from __future__ import annotations

import copy

from ..core import instructions as I
from ..core import kernels_ir as K
from ..core.approach import Approach, CostModelApproach, GreedyApproach
from ..core.ir import Program
from ..core.isel import Selection
from ..core.sysgraph import SystemGraph, tpu_v5e
from .artifact import CompiledKernel, CompileError
from .cache import (ArtifactCache, artifact_key, cacheable_approach,
                    get_default_artifact_cache)
from .pipeline import (CompileContext, LowerPass, MapPass, Pipeline,
                       SchedulePass, SelectPass, VerifyPass)

#: In-process artifact memo (the successor of ``plan_gemm``'s lru_cache):
#: fresh compiles with a reproducible approach are reused by key.
_MEMO: dict[str, CompiledKernel] = {}
_MEMO_CAP = 512


def clear_memo() -> None:
    _MEMO.clear()


def resolve_approach(approach) -> Approach:
    """Accept an Approach instance, ``None`` (greedy), or the historical
    string names (``'greedy'`` / ``'costmodel'``)."""
    if approach is None:
        return GreedyApproach()
    if isinstance(approach, str):
        if approach == "greedy":
            return GreedyApproach()
        if approach == "costmodel":
            return CostModelApproach(samples=4)
        raise ValueError(f"unknown approach name {approach!r}")
    return approach


# --------------------------------------------------------------------------- #
# Workload frontends (program + selection builders shared across the repo)
# --------------------------------------------------------------------------- #


def select_program(program: Program, isa=None, allow_transforms: bool = True,
                   approach=None) -> Selection:
    """Map + Select through the pipeline passes; raises ``CompileError`` if
    the program cannot be fully covered."""
    ctx = CompileContext(program=program, graph=tpu_v5e(1),
                         approach=approach,
                         isa=list(isa) if isa else I.tpu_isa(),
                         allow_transforms=allow_transforms)
    MapPass().run(ctx)
    SelectPass().run(ctx)
    return ctx.selection


def gemm_selection(m: int, n: int, k: int) -> tuple[Program, Selection]:
    """The canonical (m, n, k) GEMM against the MXU matmul needle."""
    prog = K.matmul(m, n, k)
    return prog, select_program(prog, [I.mxu_matmul()],
                                allow_transforms=False)


def gru_selection(batch: int, hidden: int,
                  inp: int | None = None) -> tuple[Program, Selection]:
    """The GRU cell against the full TPU ISA (fused instructions in play)."""
    prog = K.gru_cell(batch, hidden, hidden if inp is None else inp)
    return prog, select_program(prog, I.tpu_isa())


def conv_selection(**kw) -> tuple[Program, Selection]:
    """conv2d through the ISAM-TVM axis-fusion extraction onto the MXU.
    Returns (original program, selection over the transformed program)."""
    from ..core.transforms import fuse_axes_for_calls
    isa = [I.mxu_matmul()]
    orig = K.conv2d(**kw)
    prog, sel, steps = fuse_axes_for_calls(orig, isa)
    sel = Selection(sel.program, tuple(steps), sel.instrs, sel.uncovered)
    return orig, sel


_FRONTENDS = {
    "gemm": lambda **kw: gemm_selection(**kw),
    "gru": lambda **kw: gru_selection(**kw),
    "conv": lambda **kw: conv_selection(**kw),
}


# --------------------------------------------------------------------------- #
# Core compiles
# --------------------------------------------------------------------------- #


def _strip(art: CompiledKernel) -> CompiledKernel:
    """A detached copy holding only the serializable payload — what the memo
    keeps (and hands back) so it never pins live schedules/selections; a
    consumer that needs the schedule calls ``ensure_schedule()``."""
    s = copy.copy(art)
    s.program = s.graph = s.approach = s.isa = None
    s.selection = s.schedule = None
    s.meta = dict(art.meta)
    s.from_cache = True
    return s


def store_artifact(art: CompiledKernel, cache: ArtifactCache | None,
                   memoize: bool) -> CompiledKernel:
    """The one store/memo policy for every compile entry."""
    if cacheable_approach(art.approach):
        if cache is not None:
            cache.store(art)
        if memoize:
            if len(_MEMO) >= _MEMO_CAP:
                _MEMO.clear()
            _MEMO[art.key] = _strip(art)
    return art


def _finish(ctx: CompileContext, cache: ArtifactCache | None,
            memoize: bool) -> CompiledKernel:
    return store_artifact(Pipeline(passes=(SchedulePass(), VerifyPass(),
                                           LowerPass())).run(ctx),
                          cache, memoize)


def lookup_artifact(program: Program, graph: SystemGraph, approach,
                    backend: str, cache: ArtifactCache | None,
                    use_cache: bool, isa=None, allow_transforms: bool = True):
    """(key, cache, hit): the artifact key (None for an approach that is
    never cached), the persistent cache in effect (``cache``, else the
    process default; None without ``use_cache``), and the memoized or
    persisted artifact for these inputs — attached to them — or None.  The
    memo is consulted first, then the persistent cache."""
    if use_cache:
        cache = cache if cache is not None else get_default_artifact_cache()
    else:
        cache = None
    if not cacheable_approach(approach):
        return None, cache, None
    key = artifact_key(program, graph, approach, backend, isa,
                       allow_transforms)
    hit = None
    if use_cache and key in _MEMO:
        hit = _strip(_MEMO[key])
    elif cache is not None:
        hit = cache.lookup(key)
    if hit is not None:
        _attach(hit, program, graph, approach, isa, allow_transforms)
    return key, cache, hit


def compile_program(program: Program, graph: SystemGraph | None = None,
                    approach=None, isa=None, *,
                    allow_transforms: bool = True, backend: str = "cost",
                    cache: ArtifactCache | None = None, use_cache: bool = True,
                    verify: bool = True,
                    meta: dict | None = None) -> CompiledKernel:
    """Program + SystemGraph + Approach -> CompiledKernel, through the full
    Map -> Select -> Schedule -> Verify -> Lower pipeline.  ``verify=False``
    is the ``--no-verify`` escape hatch."""
    graph = graph if graph is not None else tpu_v5e(1)
    approach = resolve_approach(approach)
    isa = list(isa) if isa else I.tpu_isa()
    _, cache, hit = lookup_artifact(program, graph, approach, backend, cache,
                                    use_cache, isa, allow_transforms)
    if hit is not None:
        return hit
    ctx = CompileContext(program=program, graph=graph, approach=approach,
                         isa=isa, allow_transforms=allow_transforms,
                         backend=backend, verify=verify,
                         meta=dict(meta or {}))
    ctx.meta.setdefault("allow_transforms", allow_transforms)
    return store_artifact(Pipeline().run(ctx), cache, memoize=use_cache)


def compile_selection(selection: Selection, graph: SystemGraph,
                      approach=None, *, backend: str = "cost",
                      program: Program | None = None,
                      verify: bool = False,
                      meta: dict | None = None) -> CompiledKernel:
    """Schedule + Lower an existing Selection (no caching: this is the hot
    inner entry the search evaluators and per-chip fabric compiles use, so
    the static verifier is opt-in here — pass ``verify=True`` to gate — and
    no spans are recorded)."""
    approach = resolve_approach(approach)
    ctx = CompileContext(program=program or selection.program, graph=graph,
                         approach=approach, backend=backend,
                         meta=dict(meta or {}))
    ctx.selection = selection
    passes = ((SchedulePass(), VerifyPass(), LowerPass()) if verify
              else (SchedulePass(), LowerPass()))
    return Pipeline(passes=passes, spans=False).run(ctx)


def _compile_frontend(frontend: str, fe_args: dict, graph, approach, backend,
                      cache, use_cache, verify: bool = True) -> CompiledKernel:
    graph = graph if graph is not None else tpu_v5e(1)
    approach = resolve_approach(approach)
    # Frontend programs are cheap to rebuild; selections are not — key off
    # the program (+ the frontend's ISA/transform policy), select on a miss.
    program, isa, allow_transforms, _sel_builder = \
        _frontend_program(frontend, fe_args)
    _, cache, hit = lookup_artifact(program, graph, approach, backend, cache,
                                    use_cache, isa, allow_transforms)
    if hit is not None:
        hit.meta.setdefault("frontend", frontend)
        hit.meta.setdefault("frontend_args", dict(fe_args))
        return hit
    ctx = CompileContext(program=program, graph=graph, approach=approach,
                         isa=isa, allow_transforms=allow_transforms,
                         backend=backend, verify=verify,
                         meta={"frontend": frontend,
                               "frontend_args": dict(fe_args)})
    ctx.selection = _sel_builder()
    return _finish(ctx, cache, memoize=use_cache)


def _frontend_program(frontend: str, fe_args: dict):
    """(program, isa, allow_transforms, lazy selection builder) for one
    workload frontend — lets a cache hit skip the (expensive) mapping +
    selection entirely while keying on the exact compile inputs."""
    if frontend == "gemm":
        prog = K.matmul(fe_args["m"], fe_args["n"], fe_args["k"])
        isa = [I.mxu_matmul()]
        return prog, isa, False, lambda: select_program(
            prog, isa, allow_transforms=False)
    if frontend == "gru":
        inp = fe_args.get("inp")
        prog = K.gru_cell(fe_args["batch"], fe_args["hidden"],
                          fe_args["hidden"] if inp is None else inp)
        isa = I.tpu_isa()
        return prog, isa, True, lambda: select_program(prog, isa)
    if frontend == "conv":
        orig = K.conv2d(**fe_args)

        def build():
            _, sel = conv_selection(**fe_args)
            return sel
        return orig, [I.mxu_matmul()], True, build
    raise CompileError(f"unknown frontend {frontend!r}")


def compile_gemm(m: int, n: int, k: int, approach=None,
                 graph: SystemGraph | None = None, *,
                 backend: str = "cost", cache: ArtifactCache | None = None,
                 use_cache: bool = True, verify: bool = True) -> CompiledKernel:
    return _compile_frontend("gemm", {"m": m, "n": n, "k": k}, graph,
                             approach, backend, cache, use_cache, verify)


def compile_gru(batch: int, hidden: int, inp: int | None = None,
                approach=None, graph: SystemGraph | None = None, *,
                backend: str = "cost", cache: ArtifactCache | None = None,
                use_cache: bool = True, verify: bool = True) -> CompiledKernel:
    fe_args = {"batch": batch, "hidden": hidden}
    if inp is not None:
        fe_args["inp"] = inp
    return _compile_frontend("gru", fe_args, graph, approach, backend,
                             cache, use_cache, verify)


def compile_conv(approach=None, graph: SystemGraph | None = None, *,
                 backend: str = "cost", cache: ArtifactCache | None = None,
                 use_cache: bool = True, verify: bool = True,
                 **kw) -> CompiledKernel:
    return _compile_frontend("conv", kw, graph, approach, backend, cache,
                             use_cache, verify)


# --------------------------------------------------------------------------- #
# Cache-hit replay
# --------------------------------------------------------------------------- #


def _attach(art: CompiledKernel, program, graph, approach, isa,
            allow_transforms: bool) -> None:
    art.program = program
    art.graph = graph
    art.approach = approach
    art.isa = list(isa) if isa else None
    art.meta.setdefault("allow_transforms", allow_transforms)


def recompile_schedule(art: CompiledKernel) -> None:
    """Rebuild selection + schedule for a cache-hydrated artifact (used by
    ``CompiledKernel.ensure_schedule``).  Deterministic: the same program,
    graph and approach reproduce the cached decisions exactly.

    Fabric artifacts carry chip 0's *per-chip* schedule (what a fresh
    ``repro.fabric.simulate.compile_fabric`` attaches), so the rebuild
    re-partitions and schedules shard 0 on the single-chip graph — not the
    unsharded program on the fabric graph.  That branch is the one place
    ``repro.compile`` reaches up into ``repro.fabric`` (lazily)."""
    if art.fabric is not None:
        from ..fabric.partition import partition
        pp = partition(art.meta["kernel"], tuple(art.meta["shape"]),
                       art.fabric["axis"], art.fabric["chips"])
        shard0 = compile_selection(pp.shard_selection(pp.shards[0]),
                                   tpu_v5e(1), art.approach,
                                   program=pp.shards[0].program)
        art.selection = shard0.selection
        art.schedule = shard0.schedule
        return
    if art.selection is None:
        fe = art.meta.get("frontend")
        if fe in _FRONTENDS:
            _, art.selection = _FRONTENDS[fe](**art.meta.get(
                "frontend_args", {}))
        else:
            art.selection = select_program(
                art.program, art.isa,
                allow_transforms=bool(art.meta.get("allow_transforms", True)))
    ctx = CompileContext(program=art.program, graph=art.graph,
                         approach=art.approach, backend=art.backend)
    ctx.selection = art.selection
    SchedulePass().run(ctx)
    art.schedule = ctx.schedule
