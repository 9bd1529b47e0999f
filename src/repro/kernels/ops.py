"""Jit'd wrappers + the ISAM -> Pallas bridge.

``scheduled_gemm`` is the end-to-end TPU story: the compilation driver
(``repro.compile``: map -> select -> schedule -> lower against the v5e
system graph) decides the compute-tile shape, and that decision becomes the
Pallas BlockSpec tiling.  The compiler output *is* the kernel configuration
— no hand-written lowering rule, and no second tile source: the plan
depends on the shape alone, never on a file or the environment (a tuned
tile from ``repro.search`` is the caller's to pass as ``block=``).
``plan_gemm`` / ``plan_gru`` are thin wrappers over ``compile_gemm`` /
``compile_gru``; tiles come from the ``CompiledKernel``'s role-keyed tile
plan (derived from each mapping's ``axis_map``), never from guessed
haystack axis names.  Both planners fit the tile to the VMEM limit the
kernels are compiled under (``fit_gemm_block`` / ``fit_gru_block``), so a
planned tile always compiles.
"""
from __future__ import annotations

import jax

from ..compile import CompileError, compile_gemm, compile_gru
from .gemm import fit_gemm_block, gemm, gemm_bias_act
from .gru import fit_gru_block, gru_cell, gru_seq


def plan_gemm(m: int, n: int, k: int, approach: str = "greedy",
              use_cache: bool = True) -> tuple[tuple[int, int, int], float]:
    """Compile an (m, n, k) GEMM against the v5e graph through
    ``repro.compile``; return (chosen tile (bm, bn, bk), modeled seconds).
    ``use_cache`` governs only ``repro.compile``'s artifact memo and cache,
    as in ``compile_gemm``; the tile is the plan's either way."""
    art = compile_gemm(m, n, k, approach=approach, use_cache=use_cache)
    return fit_gemm_block(art.gemm_tile()), art.cost


def plan_gru(batch: int, hidden: int, inp: int | None = None,
             approach: str = "greedy",
             use_cache: bool = True) -> tuple[tuple[int, int], float]:
    """Compile the GRU cell through ``repro.compile``; return the (bb, bh)
    batch/hidden tile of its matmul stage, fitted to the kernel's VMEM
    limit, + the modeled seconds.  Raises ``CompileError`` if no
    matmul-shaped instruction was selected."""
    art = compile_gru(batch, hidden, inp, approach=approach,
                      use_cache=use_cache)
    for prefix in ("fused.matmul", "mxu.matmul"):
        try:
            plan = art.instr_plan(prefix)
            tile = (plan.tile_for("i"), plan.tile_for("j"))
            return (fit_gru_block(batch, hidden,
                                  hidden if inp is None else inp, tile),
                    art.cost)
        except CompileError:
            continue
    raise CompileError(
        f"GRU selection contains no matmul-shaped instruction "
        f"(have: {[p.needle for p in art.instrs]})")


def scheduled_gemm(a: jax.Array, b: jax.Array,
                   interpret: bool = False) -> jax.Array:
    """GEMM whose BlockSpec tiling was chosen by the compilation driver."""
    m, k = a.shape
    _, n = b.shape
    tile, _ = plan_gemm(m, n, k)
    return gemm(a, b, block=tile, interpret=interpret)


__all__ = [
    "gemm", "gemm_bias_act", "gru_cell", "gru_seq",
    "plan_gemm", "plan_gru", "scheduled_gemm",
]
