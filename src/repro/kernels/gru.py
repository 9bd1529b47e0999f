"""Fused GRU cell — the kernel ISAM's GRU schedule corresponds to (Fig. 4).

One ``pl.pallas_call`` computes all three gates and the state update for a
(batch-block x hidden-block) tile: six matmuls on the MXU with the gate
arithmetic fused as the VPU epilogue, hidden state kept VMEM-resident.  This
is the hand-written equivalent of the instruction stream ISAM derives
automatically (fused.matmul_bias_sigmoid + vpu ops) — the benchmark compares
the ISAM schedule's modeled cycles against a kernel-library-style unfused
op-by-op execution.

The hidden state ``h`` is passed twice: once full-width (for the U-matmul
reductions) and once as the elementwise (bb, bh) block — the two views let
BlockSpec express both access patterns of the same array.

Each grid step holds the six (E|Hp, bh) weight column blocks, double-buffered,
so the hidden tile ``bh`` is what decides whether the kernel fits VMEM:
``fit_gru_block`` sizes it from ``gru_vmem_bytes`` against the same limit the
kernel hands Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.sysgraph import V5E_VMEM_LIMIT_BYTES
from .gemm import COMPILER_PARAMS, HIGHEST, _cdiv, vmem_tile_bytes

PARAM_NAMES = ("Wr", "Ur", "Wz", "Uz", "Wn", "Un", "br", "bz", "bnx", "bnh")


def _gru_kernel(x_ref, hfull_ref, h_ref,
                wr_ref, ur_ref, wz_ref, uz_ref, wn_ref, un_ref,
                br_ref, bz_ref, bnx_ref, bnh_ref,
                out_ref):
    f32 = jnp.float32
    x = x_ref[...].astype(f32)
    hf = hfull_ref[...].astype(f32)
    h = h_ref[...].astype(f32)

    def mm(a, w_ref):
        return jnp.dot(a, w_ref[...].astype(f32), preferred_element_type=f32,
                       precision=HIGHEST)

    r = jax.nn.sigmoid(mm(x, wr_ref) + mm(hf, ur_ref) + br_ref[...])
    z = jax.nn.sigmoid(mm(x, wz_ref) + mm(hf, uz_ref) + bz_ref[...])
    n = jnp.tanh(mm(x, wn_ref) + r * (mm(hf, un_ref) + bnh_ref[...])
                 + bnx_ref[...])
    out_ref[...] = ((1 - z) * n + z * h).astype(out_ref.dtype)


def gru_vmem_bytes(bb: int, bh: int, E: int, H: int) -> int:
    """VMEM ``gru_cell`` holds for a (bb, bh) tile of a GRU with f32
    operands, input E and hidden H: every BlockSpec operand double-buffered,
    its (bb, bh) gate temporaries, and what Mosaic's full-f32 contraction
    splits off the weight blocks — up to 7.8 (max(E, H), bh) blocks in
    compiles for v5e at the DeepBench sizes, so 8 are reserved.  The
    planners assume f32; narrower operands would add the f32 copies the
    kernel makes of them."""
    Hp = _cdiv(H, bh) * bh
    operands = (vmem_tile_bytes(bb, E) + vmem_tile_bytes(bb, Hp)
                + 2 * vmem_tile_bytes(bb, bh)          # h block, out
                + 3 * vmem_tile_bytes(E, bh)           # W_r, W_z, W_n
                + 3 * vmem_tile_bytes(Hp, bh)          # U_r, U_z, U_n
                + 4 * vmem_tile_bytes(1, bh))          # biases
    temps = (8 * vmem_tile_bytes(bb, bh)
             + 8 * vmem_tile_bytes(max(E, Hp), bh))
    return 2 * operands + temps


def fit_gru_block(batch: int, hidden: int, inp: int,
                  block: tuple[int, int]) -> tuple[int, int]:
    """The largest tile under ``block`` whose ``gru_vmem_bytes`` fit
    ``V5E_VMEM_LIMIT_BYTES``.

    ``bh`` stays whole when it fits; otherwise it drops to multiples of 128
    lanes that divide the lane-padded hidden dim (no padding beyond the
    128-lane alignment), then ``bb`` halves in multiples of 8 rows."""
    bb, bh = min(block[0], batch), min(block[1], hidden)
    limit = V5E_VMEM_LIMIT_BYTES
    lanes = _cdiv(hidden, 128)
    widths = [bh] + [128 * d for d in range(lanes, 0, -1)
                     if lanes % d == 0 and 128 * d < bh]
    while True:
        for w in widths:
            if gru_vmem_bytes(bb, w, inp, hidden) <= limit:
                return bb, w
        if bb <= 8:
            raise ValueError(f"no GRU tile under {block} fits {limit} bytes "
                             f"of VMEM (batch={batch}, hidden={hidden}, "
                             f"input={inp})")
        bb = max(8, bb // 2 // 8 * 8)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gru_cell(x: jax.Array, h: jax.Array, params: dict,
             block: tuple[int, int] = (128, 128),
             interpret: bool = False) -> jax.Array:
    """One fused GRU step: x (B, E), h (B, H) -> h' (B, H)."""
    B, E = x.shape
    _, H = h.shape
    bb, bh = min(block[0], B), min(block[1], H)
    Bp, Hp = _cdiv(B, bb) * bb, _cdiv(H, bh) * bh

    x_p = jnp.pad(x, ((0, Bp - B), (0, 0))) if Bp != B else x
    h_p = jnp.pad(h, ((0, Bp - B), (0, Hp - H))) if (Bp, Hp) != (B, H) else h

    def padw(w):  # (E or H, H) -> pad output dim
        return jnp.pad(w, ((0, 0), (0, Hp - H))) if Hp != H else w

    def padu(u):  # (H, H) -> pad both
        return jnp.pad(u, ((0, Hp - H), (0, Hp - H))) if Hp != H else u

    def padb(b):  # (H,) -> (1, Hp): a 1-D block must be whole or 1024-aligned
        return jnp.pad(b, (0, Hp - H))[None] if Hp != H else b[None]

    grid = (Bp // bb, Hp // bh)
    w_spec = pl.BlockSpec((E, bh), lambda i, j: (0, j))
    u_spec = pl.BlockSpec((Hp, bh), lambda i, j: (0, j))
    b_spec = pl.BlockSpec((1, bh), lambda i, j: (0, j))

    out = pl.pallas_call(
        _gru_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, E), lambda i, j: (i, 0)),    # x
            pl.BlockSpec((bb, Hp), lambda i, j: (i, 0)),   # h (full width)
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),   # h (ew block)
            w_spec, u_spec, w_spec, u_spec, w_spec, u_spec,
            b_spec, b_spec, b_spec, b_spec,
        ],
        out_specs=pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Hp), x.dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(x_p, h_p, h_p,
      padw(params["Wr"]), padu(params["Ur"]),
      padw(params["Wz"]), padu(params["Uz"]),
      padw(params["Wn"]), padu(params["Un"]),
      padb(params["br"]), padb(params["bz"]),
      padb(params["bnx"]), padb(params["bnh"]))
    return out[:B, :H]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gru_seq(xs: jax.Array, h0: jax.Array, params: dict,
            block: tuple[int, int] = (128, 128),
            interpret: bool = False) -> jax.Array:
    """GRU over [T, B, E] — the 128-step RNN of the paper's Figure 4.
    Weights stay device-resident across steps (the recursive iteration)."""
    def step(h, x):
        return gru_cell(x, h, params, block=block, interpret=interpret), None
    h, _ = jax.lax.scan(step, h0, xs)
    return h
