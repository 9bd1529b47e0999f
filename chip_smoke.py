#!/usr/bin/env python3
"""Smoke check: the system's main paths run on a TPU, and agree with their
references there.

    python chip_smoke.py                 # one chip: serving + kernels
    python chip_smoke.py --four-chips    # four chips: sharded training only

Everything runs in this one process, which owns the chip(s).  Phases:

  (a) device check — exits non-zero, with no result line, unless JAX's
      first device is a TPU (and, with ``--four-chips``, there are four);
  (b) serving — olmo-1b at its published config through
      ``repro.launch.serve.generate``: 4 prompts of 128 tokens, 16 greedy
      new tokens, checked against the teacher-forced ``model.logits``;
  (c) ISA-mapped kernels — the Pallas GEMM at the 8 DeepBench shapes (f32
      and bf16) and the fused GRU cell at the 4 DeepBench RNN sizes,
      compiled for the chip with the tiles the compilation driver plans,
      against ``jnp.dot(precision=HIGHEST)`` and ``gru_cell_ref``;
  (t) with ``--four-chips`` only: 3 steps of olmo-1b training at full width
      through ``repro.launch.train.build_trainer`` on a (data=1, model=4)
      mesh, the first loss checked against an unsharded ``model.loss`` on
      device 0.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.  Times printed on ``[info]`` lines
are wall times of a smoke run, compilation included, not metrics.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "olmo-1b"
SERVE_BATCH, PROMPT_LEN, NEW_TOKENS = 4, 128, 16
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 512, 3

#: Logits are bf16 (8 significant bits), so one ulp of the largest logit is
#: at most 2^-7 of it.  Decode reads K/V from the cache while the
#: teacher-forced pass recomputes them, and the two run matmuls of different
#: shapes, so they round different intermediates: allow four such ulps.
LOGIT_RTOL = 2.0 ** -5
#: The loss is a mean over B*(T-1) tokens of f32 cross entropy computed
#: from bf16 logits; the sharded step sums row-parallel partial products
#: across chips where the unsharded one sums them inside one dot, so allow
#: two bf16 ulps of the loss's own magnitude.
LOSS_RTOL = 2.0 ** -7
#: f32 kernels contract at full f32 precision: their error against a
#: HIGHEST-precision reference is summation order (~1e-6 of the largest
#: output).  One bf16 pass would miss this by more than a factor of ten.
F32_TOL = 2.0 ** -14
#: bf16 GEMM outputs are rounded to bf16 once, from an f32 accumulator.
BF16_TOL = 2.0 ** -8


def fail(msg: str) -> None:
    print(f"[FAIL] {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def device_check(want_count: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    check(d.platform == "tpu", f"no TPU: JAX's first device is {d.platform}")
    check(len(devs) >= want_count,
          f"{want_count} chip(s) needed, JAX sees {len(devs)}")
    return info


# --------------------------------------------------------------------------- #
# (b) serving
# --------------------------------------------------------------------------- #


def argmax_agrees(got: np.ndarray, ref: np.ndarray, tol: float) -> bool:
    """``got``'s argmax is ``ref``'s, up to ties within ``tol`` in ``ref``
    (a near-tie may legitimately flip under rounding)."""
    pick = got.argmax(-1)
    chosen = np.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return bool((chosen >= ref.max(-1) - tol).all())


def serve_phase(seed: int, cfg=None) -> None:
    from repro.configs import get_config
    from repro.launch.serve import generate
    from repro.models import build_model

    cfg = cfg or get_config(ARCH)
    model = build_model(cfg)
    k_init, k_tok = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.jit(model.init)(k_init)
    prompts = jax.random.randint(k_tok, (SERVE_BATCH, PROMPT_LEN), 0,
                                 cfg.vocab_size, dtype=jnp.int32)

    t0 = time.perf_counter()
    toks, logits = generate(model, params, {"tokens": prompts}, NEW_TOKENS)
    toks.block_until_ready()
    wall = time.perf_counter() - t0
    print(f"[info] serve smoke time (not a metric): {wall:.3f} s for "
          f"{SERVE_BATCH}x{NEW_TOKENS} tokens, compilation included",
          flush=True)

    toks, logits = np.asarray(toks), np.asarray(logits, np.float32)
    check(toks.shape == (SERVE_BATCH, NEW_TOKENS), f"tokens {toks.shape}")
    check(logits.shape == (SERVE_BATCH, NEW_TOKENS, cfg.vocab_size),
          f"logits {logits.shape}")
    check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
          "a token is outside the vocab")
    check(bool(np.isfinite(logits).all()), "non-finite logits")

    # Teacher-forced reference on prompt + first token: its last two
    # positions are what the prefill and the first decode step predicted.
    full = jax.jit(model.logits)(
        params, {"tokens": jnp.concatenate([prompts, toks[:, :1]], 1)})
    full = np.asarray(full, np.float32)
    for name, got, ref in (("prefill", logits[:, 0], full[:, -2]),
                           ("decode step 1", logits[:, 1], full[:, -1])):
        err = float(np.abs(got - ref).max())
        tol = LOGIT_RTOL * float(np.abs(ref).max())
        print(f"[serve] {name} vs teacher-forced: max|diff|={err:.6g} "
              f"(tol {tol:.6g}), argmax equal "
              f"{int((got.argmax(-1) == ref.argmax(-1)).sum())}/{SERVE_BATCH}",
              flush=True)
        check(err <= tol, f"{name} logits differ by {err}")
        check(argmax_agrees(got, ref, tol), f"{name} argmax differs")
    print(f"[ok] serve {cfg.name}: {SERVE_BATCH} requests x {NEW_TOKENS} "
          f"tokens, sample {toks[0, :8].tolist()}", flush=True)


# --------------------------------------------------------------------------- #
# (c) ISA-mapped kernels
# --------------------------------------------------------------------------- #


def compile_for_chip(fn, *args):
    """Compile ``fn`` (a kernel with ``interpret=False``) and prove the
    Pallas kernel went to the chip's compiler, not the interpreter."""
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{getattr(fn, 'func', fn)} did not compile to a Mosaic kernel")
    return compiled


def kernel_phase(seed: int) -> None:
    from benchmarks.bench_gru import SIZES as GRU_SIZES
    from repro.kernels.gemm import gemm
    from repro.kernels.gru import PARAM_NAMES, gru_cell
    from repro.kernels.ops import plan_gemm, plan_gru
    from repro.kernels.ref import gru_cell_ref
    from repro.search.tune import DEEPBENCH_GEMM_SIZES

    rng = np.random.default_rng(seed)
    highest = jax.lax.Precision.HIGHEST
    for m, n, k in DEEPBENCH_GEMM_SIZES:
        tile, _ = plan_gemm(m, n, k, use_cache=False)
        a32 = rng.uniform(-1, 1, (m, k)).astype(np.float32)
        b32 = rng.uniform(-1, 1, (k, n)).astype(np.float32)
        for dtype, tol in ((jnp.float32, F32_TOL), (jnp.bfloat16, BF16_TOL)):
            a, b = jnp.asarray(a32, dtype), jnp.asarray(b32, dtype)
            kern = compile_for_chip(
                functools.partial(gemm, block=tile, interpret=False), a, b)
            got = np.asarray(kern(a, b), np.float32)
            want = np.asarray(jnp.dot(a.astype(jnp.float32),
                                      b.astype(jnp.float32),
                                      precision=highest))
            err = float(np.abs(got - want).max() / np.abs(want).max())
            name = f"gemm {m}x{n}x{k} {jnp.dtype(dtype).name} tile={tile}"
            check(err <= tol, f"{name}: relative error {err} > {tol}")
            print(f"[ok] {name} rel_err={err:.3g}", flush=True)

    for batch, hidden in GRU_SIZES:
        tile, _ = plan_gru(batch, hidden, use_cache=False)
        scale = hidden ** -0.5
        params = {p: jnp.asarray(rng.uniform(
            -scale, scale, (hidden, hidden) if p[0] in "WU" else (hidden,)),
            jnp.float32) for p in PARAM_NAMES}
        x = jnp.asarray(rng.uniform(-1, 1, (batch, hidden)), jnp.float32)
        h = jnp.asarray(rng.uniform(-1, 1, (batch, hidden)), jnp.float32)
        kern = compile_for_chip(
            functools.partial(gru_cell, block=tile, interpret=False),
            x, h, params)
        got = np.asarray(kern(x, h, params))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(gru_cell_ref)(x, h, params))
        err = float(np.abs(got - want).max())
        name = f"gru_cell {batch}x{hidden} tile={tile}"
        check(err <= F32_TOL, f"{name}: max error {err} > {F32_TOL}")
        print(f"[ok] {name} max_err={err:.3g}", flush=True)


# --------------------------------------------------------------------------- #
# (t) sharded training on four chips
# --------------------------------------------------------------------------- #


def train_phase(seed: int, cfg=None, batch: int = TRAIN_BATCH,
                seq: int = TRAIN_SEQ, steps: int = TRAIN_STEPS) -> None:
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, make_source
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_trainer
    from repro.optim.adamw import AdamWConfig

    cfg = cfg or get_config(ARCH)
    mesh = make_host_mesh(model=4)
    opt_cfg = AdamWConfig(warmup_steps=1, total_steps=steps)
    model, init_state, step, _ = build_trainer(cfg, opt_cfg, mesh)
    params, opt = init_state(jax.random.PRNGKey(seed))

    # Tensor parallelism must spread the weights: every device holds about
    # a quarter of them, not one device all of them.
    held = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    print(f"[train] mesh {dict(mesh.shape)}: param bytes per device "
          f"{sorted(held.values())} of {total}", flush=True)
    check(max(held.values()) <= 0.3 * total,
          "parameters are not sharded across the four devices")

    source = make_source(DataConfig(seed=seed, global_batch=batch,
                                    seq_len=seq), cfg)
    first = source.batch(0)
    dev0 = jax.devices()[0]
    ref = float(jax.jit(model.loss)(jax.device_put(params, dev0),
                                    jax.device_put(first, dev0)))

    t0 = time.perf_counter()
    carry, losses = (params, opt), []
    for s in range(steps):
        carry, metrics = step(carry, first if s == 0 else source.batch(s))
        losses.append(float(metrics["loss"]))
    wall = time.perf_counter() - t0
    print(f"[info] train smoke time (not a metric): {wall:.3f} s for "
          f"{steps} steps, compilation included", flush=True)
    print(f"[train] losses {losses}; unsharded first loss {ref}", flush=True)
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    err = abs(losses[0] - ref)
    check(err <= LOSS_RTOL * abs(ref),
          f"sharded first loss {losses[0]} vs unsharded {ref}: diff {err}")
    print(f"[ok] train {cfg.name}: {steps} steps at batch {batch} x seq "
          f"{seq} on mesh {dict(mesh.shape)}, |first - unsharded| = {err:.3g}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training phase, on 4 chips")
    args = ap.parse_args(argv)

    info = device_check(4 if args.four_chips else 1)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[info] compile cache: {enable_compile_cache()}", flush=True)
    if args.four_chips:
        train_phase(args.seed)
    else:
        serve_phase(args.seed)
        kernel_phase(args.seed)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
