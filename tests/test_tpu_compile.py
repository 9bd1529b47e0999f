"""Compiles for a described (not attached) TPU v5e: the chip's compiler
refuses here, at no chip time, what interpret mode cannot see — blocks that
do not fit VMEM, layouts Mosaic cannot tile, programs that do not fit HBM.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
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
