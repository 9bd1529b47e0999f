"""Plain float32 reference of a GEMM: C = A @ B with both operands taken
to f32 and contracted at ``Precision.HIGHEST``.  It imports nothing of the
program.

``quantize=True`` is the control: the operands rounded to float8 (e4m3,
one scale per tensor), the precision below the bf16 the configuration
states, then contracted the same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F8_MAX = 448.0


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.jit, static_argnames=("quantize",))
def matmul(a, b, quantize: bool = False):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quantize:
        a, b = _q8(a), _q8(b)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def make_operands(key, shapes, dtype=jnp.bfloat16):
    """A (m, k) and B (k, n) for each shape, uniform in [-1, 1); jit it
    (shapes and dtype static) to make them on the device in one call."""
    keys = jax.random.split(key, 2 * len(shapes))
    return [(jax.random.uniform(keys[2 * i], (m, k), jnp.float32, -1, 1).astype(dtype),
             jax.random.uniform(keys[2 * i + 1], (k, n), jnp.float32, -1, 1).astype(dtype))
            for i, (m, n, k) in enumerate(shapes)]
