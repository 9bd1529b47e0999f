"""The plain-jax reference for the traceable decoder block.

``repro.graph.trace.trace_block`` lowers a norm-free, transcendental-free
decoder block into ISAMIR kernels; this module is the *other side* of that
contract — the same block written directly in jax.numpy.  The compiled
graph's interpreted/executed output must be **bit-exact** against
``block_reference`` (the CI ``graph-smoke`` lane asserts it), which works
because:

  * every op in the block (dot products, adds, relu/max, multiplication by
    powers of two) is exact over the dyadic values the tracer's
    ``block_inputs`` generates, in *any* summation order — XLA's, numpy's,
    or the ISAMIR interpreter's;
  * the reference computes in float64 (``jax.enable_x64``) and
    casts to float32 at exactly the traced node boundaries, mirroring the
    graph interpreter's per-node dtype casts.

Keeping the reference here (not next to the tracer) mirrors the repo rule
that ``models/`` holds the jax truth the compiler tiers are validated
against.
"""
from __future__ import annotations

import numpy as np

from .config import ModelConfig


def _boundary(a):
    """One traced node boundary: round to the tensor dtype (f32)."""
    import jax.numpy as jnp
    return a.astype(jnp.float32).astype(jnp.float64)


def block_reference(inputs: dict[str, np.ndarray], cfg: ModelConfig,
                    seq_len: int) -> np.ndarray:
    """Evaluate the traceable decoder block in plain jax; returns float32.

    ``inputs`` uses the tracer's tensor names: ``x``, per-head ``wq{h}`` /
    ``wk{h}`` / ``wv{h}`` / ``wo{h}``, and ``w_gate`` / ``w_up`` /
    ``w_down``.
    """
    import jax
    import jax.numpy as jnp

    H, Dh = cfg.n_heads, cfg.hd
    halvings = (Dh.bit_length() - 1) // 2
    if 4 ** halvings != Dh:
        raise ValueError(f"head_dim {Dh} is not a power of 4")
    scale = 2.0 ** -halvings

    with jax.enable_x64(True):
        t = {k: jnp.asarray(np.asarray(v), jnp.float64)
             for k, v in inputs.items()}
        x = _boundary(t["x"])
        attn = None
        for h in range(H):
            q = _boundary(x @ t[f"wq{h}"])
            k = _boundary(x @ t[f"wk{h}"])
            v = _boundary(x @ t[f"wv{h}"])
            sraw = _boundary(q @ k.T)
            s = _boundary(jnp.maximum(sraw * scale, 0.0))
            a = _boundary(s @ v)
            p = _boundary(a @ t[f"wo{h}"])
            attn = p if attn is None else _boundary(attn + p)
        y1 = _boundary(x + attn)
        g = _boundary(jnp.maximum(_boundary(y1 @ t["w_gate"]), 0.0))
        u = _boundary(y1 @ t["w_up"])
        hid = _boundary(g + u)
        o = _boundary(hid @ t["w_down"])
        y2 = (y1 + o).astype(jnp.float32)
        return np.asarray(y2)
