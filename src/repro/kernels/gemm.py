"""Pallas TPU GEMM kernel — the MXU "matmul instruction" ISAM maps onto.

The kernel is a classic blocked matmul: grid (M/bm, N/bn, K/bk) with the
reduction dimension innermost; each grid step loads (bm, bk) and (bk, bn)
VMEM tiles via BlockSpec and accumulates into the revisited (bm, bn) output
block.  Block shapes are *parameters*: the ISAM scheduler's compute-tile
choice (scheduler.py) is forwarded here as the BlockSpec tiling — this is the
TPU-native realisation of the paper's "emit instruction stream + memory
movement": the BlockSpec pipeline IS the HBM->VMEM copy schedule.

Targeted at TPU (MXU-aligned 128x128x128 default tile).  Every kernel here
compiles for the chip unless the caller passes ``interpret=True`` — the
tests do, on CPU; nothing switches to interpret mode on its own.  Tiles are
planned to fit ``V5E_VMEM_LIMIT_BYTES`` (``fit_gemm_block``), and the same
limit is handed to Mosaic, so the plan and the compiled kernel agree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.sysgraph import V5E_VMEM_LIMIT_BYTES

#: f32 operands contract in full f32 on the MXU (Mosaic's default takes
#: fewer bf16 passes); Mosaic refuses it for bf16 operands, whose products
#: are exact in the f32 accumulator anyway.
HIGHEST = jax.lax.Precision.HIGHEST


def _precision(dtype):
    return HIGHEST if dtype == jnp.float32 else None


#: what every kernel of this package hands Mosaic
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=V5E_VMEM_LIMIT_BYTES)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def vmem_tile_bytes(rows: int, cols: int) -> int:
    """VMEM one f32 (rows, cols) block occupies: Mosaic pads the minor dim
    to 128 lanes and the second-minor to 8 sublanes."""
    return _cdiv(rows, 8) * 8 * _cdiv(cols, 128) * 128 * 4


def gemm_vmem_bytes(block: tuple[int, int, int]) -> int:
    """VMEM ``gemm``/``gemm_bias_act`` hold for one (bm, bn, bk) block of
    f32 operands: the A, B, bias and output blocks, each double-buffered by
    the pipeline, the product of one grid step, and what Mosaic's full-f32
    contraction splits off A and B (1.5x their blocks in a compile for v5e;
    2x is reserved).  The planners do not know the dtype; bf16 operands
    need less (smaller blocks, no split)."""
    bm, bn, bk = block
    operands = vmem_tile_bytes(bm, bk) + vmem_tile_bytes(bk, bn)
    blocks = operands + vmem_tile_bytes(1, bn) + vmem_tile_bytes(bm, bn)
    return 2 * blocks + vmem_tile_bytes(bm, bn) + 2 * operands


def fit_gemm_block(block: tuple[int, int, int]) -> tuple[int, int, int]:
    """``block`` shrunk until ``gemm_vmem_bytes`` fits
    ``V5E_VMEM_LIMIT_BYTES``: halve the largest of bk, bn, bm while it stays
    a multiple of 128 (bm: of 8)."""
    bm, bn, bk = block
    limit = V5E_VMEM_LIMIT_BYTES
    while gemm_vmem_bytes((bm, bn, bk)) > limit:
        if bk >= bn and bk >= 256 and bk % 256 == 0:
            bk //= 2
        elif bn >= 256 and bn % 256 == 0:
            bn //= 2
        elif bm >= 16 and bm % 16 == 0:
            bm //= 2
        else:
            raise ValueError(f"no GEMM block under {block} fits "
                             f"{limit} bytes of VMEM")
    return bm, bn, bk


def _matmul_kernel(a_ref, b_ref, c_ref, *, k_steps: int):
    """One (i, j, k) grid step: c[i, j] (+)= a[i, k] @ b[k, j]."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=c_ref.dtype,
                          precision=_precision(a_ref.dtype))


def tuned_block(m: int, n: int, k: int,
                default: tuple[int, int, int] = (128, 128, 128)
                ) -> tuple[int, int, int]:
    """Block shape for an (m, n, k) GEMM from the persistent tuning cache
    (``repro.search``), falling back to ``default`` on a cache miss.

    Tune once (``python -m repro.search.tune --suite gemm``) and every later
    process picks the winning BlockSpec up here — keyed by program
    fingerprint, system graph, backend, and jax version.

    Shapes that were *never* tuned ask the learned cost model next — when a
    process-wide model store is active (``--tuned --tuning-model``,
    ``repro.search.model.set_default_store``), the matmul-family ridge model
    ranks the tile sub-space by predicted cost and its winner becomes the
    BlockSpec.  No store / no model / any cache error keeps ``default``.
    Whatever the source, the block is fitted to the VMEM limit.
    """
    from ..search.cache import CACHE_ERRORS, clamp_tile, lookup_gemm
    try:
        rec = lookup_gemm(m, n, k)
    except CACHE_ERRORS:
        rec = None
    if rec is not None and rec.tile:
        return fit_gemm_block(clamp_tile(rec.tile, m, n, k))
    try:
        from ..search.model import predict_gemm_block
        blk = predict_gemm_block(m, n, k)
    except CACHE_ERRORS:
        blk = None
    if blk is not None:
        return fit_gemm_block(clamp_tile(blk, m, n, k))
    return default


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gemm(a: jax.Array, b: jax.Array,
         block: tuple[int, int, int] | None = None,
         interpret: bool = False) -> jax.Array:
    """C = A @ B with explicit VMEM tiling.

    ``block=(bm, bn, bk)`` is the VMEM tile shape — normally chosen by the
    ISAM scheduler (see ops.scheduled_gemm).  ``block=None`` consults the
    persistent tuning cache (``tuned_block``; resolved at trace time, so a
    cache update needs a fresh process or jit cache).  Inputs whose
    dimensions don't divide the block are padded up and the result cropped;
    zero padding is exact for the contraction.

    The kernel is named ``isam_gemm``; the pad runs under the named scope
    ``isam_gemm.pad``, the crop and cast under ``isam_gemm.crop``.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if block is None:
        block = tuned_block(m, n, k)
    bm, bn, bk = (min(block[0], m), min(block[1], n), min(block[2], k))

    acc_dtype = jnp.float32 if a.dtype in (jnp.bfloat16, jnp.float32) else a.dtype
    mp, np_, kp = _cdiv(m, bm) * bm, _cdiv(n, bn) * bn, _cdiv(k, bk) * bk
    with jax.named_scope("isam_gemm.pad"):
        a_p = jnp.pad(a, ((0, mp - m), (0, kp - k))) if (mp, kp) != (m, k) else a
        b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n))) if (kp, np_) != (k, n) else b

    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), acc_dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
        name="isam_gemm",
    )(a_p, b_p)
    with jax.named_scope("isam_gemm.crop"):
        return out[:m, :n].astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "fn"))
def gemm_bias_act(a: jax.Array, b: jax.Array, bias: jax.Array,
                  fn: str = "",
                  block: tuple[int, int, int] | None = None,
                  interpret: bool = False) -> jax.Array:
    """The paper's fused instruction: act(A @ B + bias) in one kernel —
    the epilogue runs on the VPU while the block is still VMEM-resident.
    ``block=None`` consults the tuning cache, as in ``gemm``."""
    m, k = a.shape
    _, n = b.shape
    if block is None:
        block = tuned_block(m, n, k)
    bm, bn, bk = (min(block[0], m), min(block[1], n), min(block[2], k))
    mp, np_, kp = _cdiv(m, bm) * bm, _cdiv(n, bn) * bn, _cdiv(k, bk) * bk
    with jax.named_scope("isam_gemm_bias_act.pad"):
        a_p = jnp.pad(a, ((0, mp - m), (0, kp - k))) if (mp, kp) != (m, k) else a
        b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n))) if (kp, np_) != (k, n) else b
        bias_p = jnp.pad(bias, (0, np_ - n)) if np_ != n else bias
    grid = (mp // bm, np_ // bn, kp // bk)

    def kernel(a_ref, b_ref, bias_ref, c_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            c_ref[...] = jnp.zeros_like(c_ref)

        c_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                              preferred_element_type=c_ref.dtype,
                              precision=_precision(a_ref.dtype))

        @pl.when(pl.program_id(2) == grid[2] - 1)
        def _epilogue():
            acc = c_ref[...] + bias_ref[...]
            if fn == "sigmoid":
                acc = jax.nn.sigmoid(acc)
            elif fn == "tanh":
                acc = jnp.tanh(acc)
            elif fn == "relu":
                acc = jnp.maximum(acc, 0)
            c_ref[...] = acc

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bn,), lambda i, j, kk: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
        name="isam_gemm_bias_act",
    )(a_p, b_p, bias_p)
    with jax.named_scope("isam_gemm_bias_act.crop"):
        return out[:m, :n].astype(a.dtype)
