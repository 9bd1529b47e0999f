"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
        --steps 50 --batch 8 --seq 128

Runs the full stack: config -> model -> sharded train step (host mesh) ->
deterministic data pipeline -> AdamW -> checkpoint/restart runtime with
straggler detection.  ``--smoke`` uses the reduced config so the driver runs
on CPU; on a real pod the same driver takes the production config and mesh.
"""
from __future__ import annotations

import argparse
import json

import jax

from ..checkpoint.ckpt import Checkpointer
from ..configs import ARCHS, get_config, get_smoke_config
from ..data.pipeline import DataConfig, add_frontend_stub, make_source
from ..dist.ctx import activation_sharding_ctx
from ..dist.sharding import make_activation_rules, param_shardings, replicated
from ..models.config import ModelConfig
from ..optim.adamw import AdamWConfig, init_opt_state
from ..runtime.fault_tolerance import TrainingRuntime
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh
from .steps import make_train_step


def build_trainer(cfg: ModelConfig, opt_cfg: AdamWConfig, mesh,
                  grad_accum: int = 1):
    model, train_step = make_train_step(cfg, opt_cfg, grad_accum)
    rules = make_activation_rules(mesh, cfg)

    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    p_sh = param_shardings(params_shape, mesh, cfg)
    opt_shape = jax.eval_shape(lambda: init_opt_state(params_shape))
    o_sh = type(opt_shape)(step=replicated(mesh),
                           mu=param_shardings(opt_shape.mu, mesh, cfg),
                           nu=param_shardings(opt_shape.nu, mesh, cfg))

    fn = jax.jit(train_step, in_shardings=(p_sh, o_sh, None),
                 out_shardings=(p_sh, o_sh, replicated(mesh)),
                 donate_argnums=(0, 1))

    def init_state(rng):
        with mesh, activation_sharding_ctx(rules):
            params = jax.jit(model.init, out_shardings=p_sh)(rng)
            opt = jax.jit(init_opt_state, out_shardings=o_sh)(params)
        return params, opt

    def step(carry, batch):
        params, opt = carry
        with mesh, activation_sharding_ctx(rules):
            params, opt, metrics = fn(params, opt, batch)
        return (params, opt), metrics

    return model, init_state, step, (p_sh, o_sh)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="olmo-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--save-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-fault-at", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps)
    mesh = make_host_mesh(model=args.model_axis)

    model, init_state, step, (p_sh, o_sh) = build_trainer(
        cfg, opt_cfg, mesh, args.grad_accum)

    dcfg = DataConfig(seed=17, global_batch=args.batch, seq_len=args.seq)
    source = make_source(dcfg, cfg)

    def batch_fn(s):
        b = source.batch(s)
        return add_frontend_stub(b, cfg, s, seed=dcfg.seed)

    ckpt = Checkpointer(args.ckpt_dir)
    rt = TrainingRuntime(ckpt, save_every=args.save_every)
    rt.install_preemption_handler()

    carry = None
    if args.resume:
        template = jax.eval_shape(
            lambda: (model.init(jax.random.PRNGKey(0)),
                     init_opt_state(
                         jax.eval_shape(
                             lambda: model.init(jax.random.PRNGKey(0))))))
        template = init_state(jax.random.PRNGKey(0))
        restored = rt.try_restore(template, shardings=(p_sh, o_sh))
        if restored is not None:
            carry = restored[0]
            print(f"resumed from step {restored[1]}")
    if carry is None:
        carry = init_state(jax.random.PRNGKey(0))

    losses = []

    def on_metrics(s, m, dt, slow):
        loss = float(m["loss"])
        losses.append(loss)
        flag = " SLOW" if slow else ""
        if s % 10 == 0 or s == args.steps - 1:
            print(f"step {s:5d} loss {loss:.4f} gnorm "
                  f"{float(m['grad_norm']):.3f} {dt*1e3:.0f}ms{flag}",
                  flush=True)

    carry = rt.run(carry, step, batch_fn, args.steps, on_metrics,
                   inject_fault_at=args.inject_fault_at)
    print(json.dumps({"final_loss": losses[-1] if losses else None,
                      "first_loss": losses[0] if losses else None,
                      "steps_run": len(losses),
                      "slow_steps": len(rt.straggler.slow_steps),
                      "resumed": rt.state.resumed}))
    return losses


if __name__ == "__main__":
    main()
