#!/usr/bin/env python3
"""Count the compiled operations of a model's prefill and decode step, or
of the Pallas GEMM, by the program's named scopes, from the ``op_name``
metadata of the optimized HLO.

    PYTHONPATH=src python scripts/scope_ops.py --arch olmo-1b --smoke
    PYTHONPATH=src python scripts/scope_ops.py --gemm 5124x700x2048

Prints one JSON object: for each program, {scope: [instructions, bytes of
their results]}, each instruction counted under the innermost named scope
of its ``op_name`` (``-`` for none).  The HLO is the default backend's: on
a TPU the chip's own fusions, named as in a device trace; on a CPU the
GEMM runs the Pallas interpreter.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

#: named scopes of the model and the kernels (``models/``, ``kernels/``)
SCOPES = ("embed", "weight_cast", "attn", "kv_update", "ffn", "final_norm",
          "head", "mla.latent", "mla.absorb", "mla.attend", "moe.route",
          "moe.experts", "moe.shared", "isam_gemm.pad", "isam_gemm.crop", "isam_gemm",
          "isam_gemm_bias_act.pad", "isam_gemm_bias_act.crop",
          "isam_gemm_bias_act", "isam_gru_cell")
BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
         "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
         "u64": 8}
INSTR = re.compile(r"^\s*(?:ROOT )?%?\S+ = (\w+)\[([\d,]*)\]")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def tally(hlo_text: str) -> dict:
    """{scope: [instructions, result bytes]} over optimized HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = OP_NAME.search(line)
        if not m:
            continue
        hit = [p for p in m.group(1).split("/") if p in SCOPES]
        scope = hit[-1] if hit else "-"
        shape = INSTR.match(line)
        nbytes = 0
        if shape and shape.group(1) in BYTES:
            nbytes = BYTES[shape.group(1)]
            for d in filter(None, shape.group(2).split(",")):
                nbytes *= int(d)
        c = out.setdefault(scope, [0, 0])
        c[0] += 1
        c[1] += nbytes
    return out


def model_programs(arch: str, smoke: bool, batch: int, prompt_len: int):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_smoke_config
    from repro.models import build_model

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
    max_len = prompt_len + 8

    def prefill(p, t):
        return model.prefill(p, {"tokens": t}, max_len=max_len)

    cache = jax.eval_shape(prefill, params, tokens)[0]
    yield "prefill", jax.jit(prefill).lower(params, tokens)
    yield "decode_step", jax.jit(model.decode_step).lower(
        params, cache, jax.ShapeDtypeStruct((batch,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))


def gemm_programs(shape: str):
    import jax
    import jax.numpy as jnp

    from repro.kernels.gemm import gemm

    m, n, k = (int(x) for x in shape.split("x"))
    interpret = jax.default_backend() != "tpu"
    a = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((k, n), jnp.bfloat16)
    yield "gemm", jax.jit(lambda a, b: gemm(a, b, interpret=interpret)).lower(a, b)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gemm", default=None, metavar="MxNxK",
                    help="tally the Pallas GEMM at this shape instead")
    args = ap.parse_args(argv)
    progs = (gemm_programs(args.gemm) if args.gemm else
             model_programs(args.arch, args.smoke, args.batch, args.prompt_len))
    print(json.dumps({name: tally(lowered.compile().as_text())
                      for name, lowered in progs}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
