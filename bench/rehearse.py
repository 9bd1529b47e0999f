#!/usr/bin/env python3
"""Compile each cell's device programs at their real sizes for a described
(not attached) TPU v5e, and print what the chip's compiler says of their
memory.  Needs no chip; run on a CPU host:

    JAX_PLATFORMS=cpu python bench/rehearse.py

The serving cells run ``generate``, which calls ``model.prefill`` and
``model.decode_step`` eagerly; their scans compile to the same programs as
the jitted calls compiled here, with the f32 weights as arguments.
"""
from __future__ import annotations

import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench.harness import load_cell, load_module, model_config  # noqa: E402


def placed(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def report(name, compiled) -> dict:
    m = compiled.memory_analysis()
    row = {"program": name,
           "argument_bytes": m.argument_size_in_bytes,
           "output_bytes": m.output_size_in_bytes,
           "temp_bytes": m.temp_size_in_bytes,
           "alias_bytes": m.alias_size_in_bytes}
    print(json.dumps(row), flush=True)
    return row


def serve_programs(cell, one):
    from repro.models import build_model
    cfg = model_config(cell["config_file"])
    tr = cell["traffic"]
    model = build_model(cfg)
    B, T, n = tr["batch"], tr["prompt_len"], tr["new_tokens"]
    params = placed(jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))),
                    one)
    toks = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one)
    pre = jax.jit(functools.partial(model.prefill, max_len=T + n)) \
        .lower(params, {"tokens": toks}).compile()
    report(f"{cell['name']} prefill", pre)
    if n > 1:
        cache = placed(jax.eval_shape(lambda: model.init_cache(B, T + n)), one)
        step = jax.jit(model.decode_step).lower(
            params, cache, jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one)).compile()
        report(f"{cell['name']} decode_step", step)


def gemm_programs(cell, one):
    from repro.kernels.ops import plan_gemm
    shapes = cell["config_file"]["shapes"]
    tiles = [plan_gemm(m, n, k, use_cache=False)[0] for m, n, k in shapes]
    rounds = cell["traffic"]["rounds_per_program"]
    drv = load_module("drivers", "gemm_suite")
    program = drv.kernel_program(tiles, rounds, False)
    operands = [(jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one),
                 jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one))
                for m, n, k in shapes]
    calls = len(drv.kernel_op_names(program, operands))
    if calls != rounds * len(shapes):
        raise RuntimeError(f"{calls} kernel calls in the program, not {rounds * len(shapes)}")
    report(f"gemm program of {rounds} rounds of {len(shapes)} shapes, tiles {tiles}",
           program.lower(operands).compile())


def main(argv=None) -> int:
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    names = argv if argv else sorted(
        f[:-5] for f in os.listdir(os.path.join(ROOT, "bench", "workloads")))
    for name in names:
        cell = load_cell(name)
        if cell["driver"] == "gemm_suite":
            gemm_programs(cell, one)
        elif cell["driver"] == "serve_batch":
            serve_programs(cell, one)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
