"""Compiles for a described (not attached) TPU v5e: the chip's compiler
refuses here, at no chip time, what interpret mode cannot see — blocks that
do not fit VMEM, layouts Mosaic cannot tile, programs that do not fit HBM.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import collections
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.bench_gru import SIZES as GRU_SIZES
from repro.configs import get_config
from repro.core.sysgraph import V5E_HBM_BYTES
from repro.kernels.gemm import gemm
from repro.kernels.gru import PARAM_NAMES, gru_cell
from repro.kernels.ops import plan_gemm, plan_gru
from repro.launch.steps import (eval_shape_cache, eval_shape_params,
                                make_serve_step)
from repro.search.tune import DEEPBENCH_GEMM_SIZES


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def shapes(sharding, tree):
    """``tree``'s shapes placed by ``sharding`` (one sharding, or a tree of
    them mirroring ``tree``)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree, sharding)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,k", [DEEPBENCH_GEMM_SIZES[0],
                                   DEEPBENCH_GEMM_SIZES[4],
                                   DEEPBENCH_GEMM_SIZES[7]])
def test_gemm_compiles_for_v5e(one_chip, m, n, k, dtype):
    tile, _ = plan_gemm(m, n, k, use_cache=False)
    a, b = shapes(one_chip, (jax.ShapeDtypeStruct((m, k), dtype),
                             jax.ShapeDtypeStruct((k, n), dtype)))
    text = jax.jit(lambda a, b: gemm(a, b, block=tile)).lower(a, b) \
        .compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch,hidden", GRU_SIZES)
def test_gru_cell_compiles_for_v5e(one_chip, batch, hidden):
    tile, _ = plan_gru(batch, hidden, use_cache=False)
    f32 = jnp.float32
    params = {p: jax.ShapeDtypeStruct(
        (hidden, hidden) if p[0] in "WU" else (hidden,), f32)
        for p in PARAM_NAMES}
    x, h, params = shapes(one_chip, (jax.ShapeDtypeStruct((batch, hidden), f32),
                                     jax.ShapeDtypeStruct((batch, hidden), f32),
                                     params))
    text = jax.jit(lambda x, h, p: gru_cell(x, h, p, block=tile)) \
        .lower(x, h, params).compile().as_text()
    assert "tpu_custom_call" in text


def test_olmo_decode_step_fits_one_v5e(one_chip):
    """olmo-1b at published width, batch 8 against a 2048-deep cache: the
    serving step's arguments, temporaries and outputs fit one chip's HBM."""
    cfg = get_config("olmo-1b")
    model, serve_step = make_serve_step(cfg)
    _, params = eval_shape_params(cfg)
    cache = eval_shape_cache(cfg, 8, 2048)
    args = shapes(one_chip, (params, cache,
                             jax.ShapeDtypeStruct((8,), jnp.int32),
                             jax.ShapeDtypeStruct((), jnp.int32)))
    mem = jax.jit(serve_step).lower(*args).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > cfg.param_count() * 4
    assert total < V5E_HBM_BYTES, total


def test_olmo_served_decode_step_donates_its_cache(one_chip):
    """The decode step that ``generate`` dispatches (``DecoderLM``'s compiled
    entry point) at the batch-decode cell's size, 32 x 544: its output cache
    takes the donated cache's buffer, and the step fits one chip's HBM."""
    cfg = get_config("olmo-1b")
    model, params = eval_shape_params(cfg)
    cache = eval_shape_cache(cfg, 32, 544)
    args = shapes(one_chip, (params, cache,
                             jax.ShapeDtypeStruct((32,), jnp.int32),
                             jax.ShapeDtypeStruct((), jnp.int32)))
    mem = model._decode_jit.lower(*args).compile().memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes == cache_bytes
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


#: one instruction of an HLO module's text: name, result type, opcode, operands
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\(([^)]*)")
#: opcodes that name or forward a buffer without writing one
_NO_WRITE = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def _first_array(hlo_type: str) -> tuple[str, int]:
    """The first array of an HLO result type, as ``dtype[dims]`` with
    ``{S(1)}`` appended when its layout puts it in memory space 1, and its
    element count."""
    m = re.search(r"(\w+\[([\d,]*)\])(\{[^}]*\})?", hlo_type)
    n = math.prod(int(d) for d in m.group(2).split(",") if d)
    return m.group(1) + ("{S(1)}" if "S(1)" in (m.group(3) or "") else ""), n


def cache_results(text: str, sizes: set[int], row: int):
    """Instructions of a compiled module (outside fused computations, which
    write no buffer of their own) whose result has one of ``sizes``
    elements, as (in-place row updates, the others): a row update is a
    dynamic-update-slice, or a fusion rooted in one, that writes ``row``
    elements into its operand."""
    comps, cur = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            cur = comps.setdefault(
                re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1), [])
        elif cur is not None and (m := _INSTR.match(line)):
            name, ty, op, args = m.groups()
            cur.append((name, ty, op, re.findall(r"%([\w.\-]+)", args),
                        line.lstrip().startswith("ROOT"), line))
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))

    def writes_row(instrs, op, args, line):
        if op == "fusion":
            instrs = comps[re.search(r"calls=%([\w.\-]+)", line).group(1)]
            root = next(i for i in instrs if i[4])
            op, args = root[2], root[3]
        types = {i[0]: i[1] for i in instrs}
        return (op == "dynamic-update-slice"
                and _first_array(types[args[1]])[1] == row)

    rows, others = [], []
    for cname, instrs in comps.items():
        if cname in fused:
            continue
        for name, ty, op, args, _, line in instrs:
            array, n = _first_array(ty)
            if op in _NO_WRITE or n not in sizes:
                continue
            (rows if writes_row(instrs, op, args, line) else others).append(
                (name, array))
    return rows, others


def _served_decode_step(one_chip, cfg, batch, seq):
    model, params = eval_shape_params(cfg)
    cache = eval_shape_cache(cfg, batch, seq)
    args = shapes(one_chip, (params, cache,
                             jax.ShapeDtypeStruct((batch,), jnp.int32),
                             jax.ShapeDtypeStruct((), jnp.int32)))
    return cache, model._decode_jit.lower(*args).compile()


@pytest.mark.parametrize("arch,n_layers,batch,seq,layer_reads", [
    ("olmo-1b", 16, 32, 544, 0),
    # GQA, 28 heads over 4: each layer's K and V are staged once in memory
    # space 1 (the v5e's VMEM) as the grouped dot's operand
    ("qwen2-7b", 4, 32, 2048, 2),
])
def test_served_decode_step_writes_one_cache_row_per_layer(
        one_chip, arch, n_layers, batch, seq, layer_reads):
    """The decode step ``generate`` dispatches, at the batch-decode cell's
    size for olmo-1b: the stacked KV cache is carried through the layer
    scan and updated in place, one row per layer of K and of V.  No other
    instruction writes a buffer the size of the stacked cache or of one
    layer's cache (bar GQA's staged reads), and the temporaries stay under
    2.5 GB (4.71 GB for olmo-1b when the scan mapped the cache)."""
    cfg = get_config(arch).scaled(n_layers=n_layers)
    cache, compiled = _served_decode_step(one_chip, cfg, batch, seq)
    k = cache["kv"]["k"]
    assert k.shape == (n_layers, seq, cfg.n_kv_heads, batch, cfg.hd)
    rows, others = cache_results(compiled.as_text(), {k.size, k.size // n_layers},
                                 row=cfg.n_kv_heads * batch * cfg.hd)
    assert len(rows) == 2, rows
    layer = f"bf16[1,{seq},{cfg.n_kv_heads},{batch},{cfg.hd}]{{S(1)}}"
    assert [ty for _, ty in others] == [layer] * layer_reads, others
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


#: cache-sized results of DeepSeek's served decode step (``cache_results``)
LATENT_CACHE_WRITES = {
    "bf16[4,32,4224,512]": 3, "bf16[32,4224,512]": 3,
    "bf16[4,32,4224,64]": 3, "bf16[4,32,4224,64]{S(1)}": 2,
    "bf16[32,4224,64]": 8, "bf16[32,4224,64]{S(1)}": 4,
    "bf16[1,32,4224,64]{S(1)}": 2,
}


def test_deepseek_served_decode_step_fits_and_donates_its_cache(one_chip):
    """DeepSeek-V2-Lite's served decode step at the long-decode cell's
    size (the leading dense layer and 4 MoE layers holding 16 of 64
    experts, 32 x 4224 positions): the latent cache of both layer groups
    takes the donated buffers, the grouped expert matmul compiles for the
    chip, and the step fits one chip's HBM."""
    cfg = get_config("deepseek-v2-lite").scaled(n_layers=5, held_experts=16)
    model, params = eval_shape_params(cfg)
    cache = eval_shape_cache(cfg, 32, 4224)
    assert set(cache) == {"kv", "kv_dense"}
    args = shapes(one_chip, (params, cache,
                             jax.ShapeDtypeStruct((32,), jnp.int32),
                             jax.ShapeDtypeStruct((), jnp.int32)))
    compiled = model._decode_jit.lower(*args).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert cache_bytes == 32 * 4224 * 5 * (512 + 64) * 2
    assert mem.alias_size_in_bytes == cache_bytes
    assert "ragged" in compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
    # the latent cache keeps its mapped scan: the same cache-sized writes
    sizes = {n for a in jax.tree.leaves(cache)
             for n in (a.size, a.size // a.shape[0])}
    _, written = cache_results(compiled.as_text(), sizes, row=-1)
    assert collections.Counter(ty for _, ty in written) == LATENT_CACHE_WRITES


def _serving_weights(one_chip, model, params):
    """The shapes of the weights a decoding ``generate`` call passes to the
    programs (``DecoderLM.serving_weights``), placed on ``one_chip``."""
    return shapes(one_chip, jax.eval_shape(model.serving_weights, params))


def test_olmo_served_decode_step_on_cast_weights_casts_nothing(one_chip):
    """olmo-1b's decode step at the batch-decode cell's size, 32 x 544,
    given the weights ``generate`` casts once per call: no instruction
    converts an argument, and the temporaries, which were the f32 weight
    stacks cast whole (2.148 GB), all but vanish."""
    cfg = get_config("olmo-1b")
    model, params = eval_shape_params(cfg)
    cache = eval_shape_cache(cfg, 32, 544)
    args = shapes(one_chip, (cache, jax.ShapeDtypeStruct((32,), jnp.int32),
                             jax.ShapeDtypeStruct((), jnp.int32)))
    compiled = model._decode_jit.lower(
        _serving_weights(one_chip, model, params), *args).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    arguments = {m.group(1) for line in entry.splitlines()
                 if (m := _INSTR.match(line)) and m.group(3) == "parameter"}
    assert len(arguments) == len(jax.tree.leaves((params, cache))) + 2
    converted = [m.group(1) for line in text.splitlines()
                 if (m := _INSTR.match(line)) and m.group(3) == "convert"
                 and arguments & set(re.findall(r"%([\w.\-]+)", m.group(4)))]
    assert converted == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_deepseek_served_programs_on_cast_weights_fit_one_v5e(one_chip, program):
    """The DeepSeek cell's prefill (32 x 4096 into 4224 positions) and
    decode step given the weights ``generate`` casts once per call fit one
    chip's HBM beside the f32 weights the caller keeps (4.72 GB)."""
    cfg = get_config("deepseek-v2-lite").scaled(n_layers=5, held_experts=16)
    model, params = eval_shape_params(cfg)
    weights = _serving_weights(one_chip, model, params)
    if program == "prefill":
        batch = shapes(one_chip, {"tokens": jax.ShapeDtypeStruct((32, 4096), jnp.int32)})
        compiled = model._prefill_jit.lower(weights, batch, max_len=4224).compile()
    else:
        args = shapes(one_chip, (eval_shape_cache(cfg, 32, 4224),
                                 jax.ShapeDtypeStruct((32,), jnp.int32),
                                 jax.ShapeDtypeStruct((), jnp.int32)))
        compiled = model._decode_jit.lower(weights, *args).compile()
    mem = compiled.memory_analysis()
    stored = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes < stored      # the bf16 copy, not the f32
    total = (stored + mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


def test_olmo_train_step_fits_four_v5e(topo):
    """olmo-1b training at full width, tensor-parallel over a (data=1,
    model=4) mesh with the rules ``launch/train.py:build_trainer`` uses: the
    state spreads over the four chips (collectives appear, no device holds
    it all) and each chip's share fits its HBM."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.dist.ctx import activation_sharding_ctx
    from repro.dist.sharding import (make_activation_rules, param_shardings,
                                     replicated)
    from repro.launch.steps import eval_shape_opt_state, make_train_step

    cfg = get_config("olmo-1b")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    _, train_step = make_train_step(cfg)
    _, params = eval_shape_params(cfg)
    opt = eval_shape_opt_state(params)
    p_sh = param_shardings(params, mesh, cfg)
    o_sh = type(opt)(step=replicated(mesh),
                     mu=param_shardings(opt.mu, mesh, cfg),
                     nu=param_shardings(opt.nu, mesh, cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 512), jnp.int32,
                                            sharding=replicated(mesh))}
    fn = jax.jit(train_step, in_shardings=(p_sh, o_sh, None),
                 out_shardings=(p_sh, o_sh, replicated(mesh)),
                 donate_argnums=(0, 1))
    with mesh, activation_sharding_ctx(make_activation_rules(mesh, cfg)):
        compiled = fn.lower(shapes(p_sh, params), shapes(o_sh, opt),
                            batch).compile()
    mem = compiled.memory_analysis()
    state = 3 * cfg.param_count() * 4          # f32 params + two moments
    assert mem.argument_size_in_bytes < state / 3
    assert "all-reduce" in compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
