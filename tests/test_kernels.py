"""Pallas kernel tests: shape/dtype sweeps in interpret mode against the
pure-jnp oracles, plus the ISAM->BlockSpec bridge."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sysgraph import V5E_VMEM_LIMIT_BYTES
from repro.kernels import ops, ref
from repro.kernels.gemm import (fit_gemm_block, gemm, gemm_bias_act,
                                gemm_vmem_bytes)
from repro.kernels.gru import PARAM_NAMES, gru_cell, gru_seq, gru_vmem_bytes


def rand(rng, shape, dtype):
    x = rng.uniform(-1, 1, size=shape)
    return jnp.asarray(x, dtype=dtype)


TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,k", [
    (128, 128, 128),            # exact MXU tile
    (256, 128, 384),            # multi-tile, divisible
    (64, 48, 96),               # sub-tile
    (130, 70, 190),             # ragged: exercises padding
    (1, 128, 512),              # skinny (decode-like)
    (512, 1, 64),               # skinny the other way
])
def test_gemm_matches_ref(m, n, k, dtype):
    rng = np.random.default_rng(m * 7 + n * 3 + k)
    a, b = rand(rng, (m, k), dtype), rand(rng, (k, n), dtype)
    got = gemm(a, b, interpret=True)
    want = ref.gemm_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("block", [(32, 32, 32), (64, 128, 32), (128, 64, 256)])
def test_gemm_block_sweep(block):
    rng = np.random.default_rng(0)
    a, b = rand(rng, (160, 96), jnp.float32), rand(rng, (96, 224), jnp.float32)
    got = gemm(a, b, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.gemm_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fn", ["", "sigmoid", "tanh", "relu"])
def test_gemm_bias_act(fn):
    rng = np.random.default_rng(1)
    a, b = rand(rng, (96, 64), jnp.float32), rand(rng, (64, 80), jnp.float32)
    bias = rand(rng, (80,), jnp.float32)
    got = gemm_bias_act(a, b, bias, fn=fn, interpret=True)
    want = ref.gemm_bias_act_ref(a, b, bias, fn=fn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def make_gru_params(rng, E, H, dtype=jnp.float32):
    p = {}
    for name in PARAM_NAMES:
        if name.startswith("W"):
            p[name] = rand(rng, (E, H), dtype)
        elif name.startswith("U"):
            p[name] = rand(rng, (H, H), dtype)
        else:
            p[name] = rand(rng, (H,), dtype)
    return p


@pytest.mark.parametrize("B,E,H", [(4, 16, 32), (8, 64, 64), (3, 10, 50)])
def test_gru_cell_matches_ref(B, E, H):
    rng = np.random.default_rng(B + E + H)
    p = make_gru_params(rng, E, H)
    x, h = rand(rng, (B, E), jnp.float32), rand(rng, (B, H), jnp.float32)
    got = gru_cell(x, h, p, block=(4, 32), interpret=True)
    want = ref.gru_cell_ref(x, h, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gru_seq_matches_ref():
    rng = np.random.default_rng(9)
    T, B, E, H = 5, 4, 12, 24
    p = make_gru_params(rng, E, H)
    xs = rand(rng, (T, B, E), jnp.float32)
    h0 = rand(rng, (B, H), jnp.float32)
    got = gru_seq(xs, h0, p, block=(4, 24), interpret=True)
    want = ref.gru_seq_ref(xs, h0, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_isam_plans_gemm_tile():
    """The ISAM schedule must produce an MXU-aligned tile for a big GEMM and
    a clipped tile for a small one."""
    tile, secs = ops.plan_gemm(1024, 1024, 1024)
    assert tile[0] == 128
    assert tile[1] % 128 == 0      # j grows into the VMEM budget, MXU-aligned
    assert tile[2] >= 128          # k streams as deep as VMEM allows
    assert secs > 0
    tile_small, _ = ops.plan_gemm(32, 32, 32)
    assert tile_small == (32, 32, 32)


def test_scheduled_gemm_executes():
    rng = np.random.default_rng(2)
    a = rand(rng, (192, 64), jnp.float32)
    b = rand(rng, (64, 160), jnp.float32)
    got = ops.scheduled_gemm(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.gemm_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


def test_kernels_compile_for_the_chip_unless_told_to_interpret():
    """No silent interpret-mode fallback: off a TPU, a kernel called
    without interpret=True fails instead of running in the interpreter."""
    if jax.default_backend() == "tpu":
        pytest.skip("runs the kernel on the chip here")
    a = jnp.ones((128, 128), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        jax.block_until_ready(gemm(a, a, block=(128, 128, 128)))


@pytest.mark.parametrize("batch,hidden", [(32, 512), (32, 1024), (16, 1536),
                                          (32, 1792), (128, 2560)])
def test_planned_gru_tile_fits_vmem_limit(batch, hidden):
    tile, _ = ops.plan_gru(batch, hidden, use_cache=False)
    assert gru_vmem_bytes(*tile, hidden, hidden) <= V5E_VMEM_LIMIT_BYTES
    assert tile[1] == hidden or tile[1] % 128 == 0


def test_fit_gemm_block_shrinks_an_oversized_tile():
    big = (512, 4096, 4096)
    assert gemm_vmem_bytes(big) > V5E_VMEM_LIMIT_BYTES
    fitted = fit_gemm_block(big)
    assert gemm_vmem_bytes(fitted) <= V5E_VMEM_LIMIT_BYTES
    assert all(f <= b for f, b in zip(fitted, big))
    assert fit_gemm_block((128, 128, 1024)) == (128, 128, 1024)
