"""repro.compile — the pass-based compilation driver.

One pipeline behind every entry point (the paper's single frontend → IR
passes → backend story, VTA/DL-compiler-survey style):

    Program ──Map──▶ candidates ──Select──▶ Selection ──Schedule──▶
        Schedule ──Lower──▶ CompiledKernel

  * ``pipeline``  — ``Pipeline`` + the Map / Select / Schedule / Lower
                    passes over a ``CompileContext``;
  * ``artifact``  — the serializable ``CompiledKernel``: role-keyed tile
                    plan (derived from each mapping's ``axis_map``), Pallas
                    lowering config, modeled cost, fabric plan;
  * ``cache``     — persistent artifact cache keyed by (program fp, sysgraph
                    fp, approach fp, backend, jax version), and those
                    fingerprints (shared with ``repro.search``);
  * ``driver``    — ``compile_program`` / ``compile_gemm`` / ``compile_gru``
                    / ``compile_conv`` / ``compile_selection`` and the
                    workload frontends shared by ``repro.kernels``,
                    ``repro.search`` and ``repro.fabric``.

The package sits below ``repro.kernels`` and above ``repro.core``: it
imports nothing from the modeled add-ons (``search``, ``fabric``,
``graph``, ``serve``), which build on it.

CLI: ``python -m repro.compile --kernel gemm --shape 1024x1024x1024``.
"""
from .artifact import CompiledKernel, CompileError, InstrPlan
from .cache import (ArtifactCache, artifact_key, default_artifact_cache_path,
                    get_default_artifact_cache, set_default_artifact_cache)
from .driver import (compile_conv, compile_gemm, compile_gru,
                     compile_program, compile_selection, conv_selection,
                     gemm_selection, gru_selection, resolve_approach,
                     select_program)
from .pipeline import (CompileContext, LowerPass, MapPass, Pipeline,
                       SchedulePass, SelectPass)

__all__ = [
    "ArtifactCache", "CompileContext", "CompiledKernel", "CompileError",
    "InstrPlan", "LowerPass", "MapPass", "Pipeline", "SchedulePass",
    "SelectPass", "artifact_key", "compile_conv", "compile_gemm",
    "compile_gru", "compile_program", "compile_selection", "conv_selection",
    "default_artifact_cache_path", "gemm_selection",
    "get_default_artifact_cache", "gru_selection", "resolve_approach",
    "select_program", "set_default_artifact_cache",
]
