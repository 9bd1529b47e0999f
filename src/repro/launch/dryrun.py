import os
# A CPU-only tool: 512 placeholder host devices, and never the chip.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape x mesh) cell this lowers + compiles
the real train/prefill/serve step with production shardings against 512
placeholder host devices, then records:

  * memory_analysis()  — proves the cell fits per-device HBM,
  * cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * collective traffic — parsed from the optimized HLO text,
  * roofline terms     — compute / memory / collective seconds (v5e).

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCHS, cell_applicable, get_config, input_specs
from ..dist.ctx import activation_sharding_ctx
from ..dist.sharding import (batch_shardings, cache_shardings,
                             make_activation_rules, param_shardings,
                             replicated)
from ..models.config import SHAPES
from .hlo_analysis import TARGET_ROOFLINES, roofline_terms
from .hlo_flops import analyse_hlo
from .mesh import make_production_mesh
from .steps import (eval_shape_cache, eval_shape_opt_state,
                    eval_shape_params, make_prefill_step, make_serve_step,
                    make_train_step)


def _with_sharding(shape_tree, sharding_tree):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shape_tree, sharding_tree)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict | None = None):
    """Build + lower + compile one cell; returns (compiled, lowered, meta)."""
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.scaled(**overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size

    rules = make_activation_rules(mesh, cfg)
    model, params_shape = eval_shape_params(cfg)
    p_sh = param_shardings(params_shape, mesh, cfg)
    params_in = _with_sharding(params_shape, p_sh)
    specs = input_specs(cfg, shape)
    b_sh = batch_shardings(specs, mesh)
    batch_in = _with_sharding(specs, b_sh)

    if shape.kind == "train":
        _, train_step = make_train_step(cfg)
        opt_shape = eval_shape_opt_state(params_shape)
        # moments mirror the param shardings; step counter replicated
        o_sh = type(opt_shape)(
            step=replicated(mesh),
            mu=param_shardings(opt_shape.mu, mesh, cfg),
            nu=param_shardings(opt_shape.nu, mesh, cfg))
        opt_in = _with_sharding(opt_shape, o_sh)
        fn = jax.jit(train_step,
                     in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, replicated(mesh)),
                     donate_argnums=(0, 1))
        with mesh, activation_sharding_ctx(rules):
            lowered = fn.lower(params_in, opt_in, batch_in)
    elif shape.kind == "prefill":
        _, prefill_step = make_prefill_step(cfg, max_len=shape.seq_len)
        cache_shape = eval_shape_cache(cfg, shape.global_batch, shape.seq_len)
        c_sh = cache_shardings(cache_shape, mesh, cfg)
        fn = jax.jit(prefill_step,
                     in_shardings=(p_sh, b_sh),
                     out_shardings=(c_sh, replicated(mesh)))
        with mesh, activation_sharding_ctx(rules):
            lowered = fn.lower(params_in, batch_in)
    else:  # decode
        _, serve_step = make_serve_step(cfg)
        cache_shape = eval_shape_cache(cfg, shape.global_batch, shape.seq_len)
        c_sh = cache_shardings(cache_shape, mesh, cfg)
        cache_in = _with_sharding(cache_shape, c_sh)
        tok_in = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32,
                                      sharding=b_sh["tokens"])
        pos_in = jax.ShapeDtypeStruct((), jnp.int32,
                                      sharding=replicated(mesh))
        fn = jax.jit(serve_step,
                     in_shardings=(p_sh, c_sh, b_sh["tokens"],
                                   replicated(mesh)),
                     out_shardings=(replicated(mesh), c_sh),
                     donate_argnums=(1,))
        with mesh, activation_sharding_ctx(rules):
            lowered = fn.lower(params_in, cache_in, tok_in, pos_in)

    compiled = lowered.compile()
    return compiled, lowered, {"chips": chips, "cfg": cfg, "shape": shape}


def analyse(compiled, lowered, meta, elapsed: float,
            target: str = "tpu_v5e") -> dict:
    chips = meta["chips"]
    cfg, shape = meta["cfg"], meta["shape"]
    out: dict = {"arch": cfg.name, "shape": shape.name, "chips": chips,
                 "kind": shape.kind, "target": target,
                 "compile_s": round(elapsed, 2)}

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    out["xla_cost_analysis"] = {"flops": float(cost.get("flops", 0.0)),
                                "bytes": float(cost.get("bytes accessed", 0.0))}

    try:
        mem = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not support it
        out["memory"] = {"error": str(e)}

    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    # trip-count-aware analysis (XLA counts while bodies once; our models
    # scan over layers, so the multiplier matters — see hlo_flops.py)
    stats = analyse_hlo(text)
    flops = stats.flops
    nbytes = stats.bytes_accessed
    out["hlo_flops"] = flops
    out["hlo_bytes"] = nbytes
    out["while_trip_counts"] = sorted(stats.while_trip_counts)
    out["collectives"] = {
        "total_bytes": stats.collective_bytes,
        "total_count": sum(stats.collective_counts.values()),
        "bytes_by_kind": dict(stats.collective_bytes_by_kind),
        "count_by_kind": dict(stats.collective_counts),
    }

    # the parsed module is the per-device SPMD program; scale to the job.
    out["roofline"] = roofline_terms(flops * chips, nbytes * chips,
                                     stats.collective_bytes * chips, chips,
                                     target=target)
    # Model FLOPs: 6 * N_active * D(tokens) for training; decode counts 1 tok
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        out["model_flops"] = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        out["model_flops"] = 2 * n_active * tokens
    else:
        out["model_flops"] = 2 * n_active * shape.global_batch
    total_hlo = flops * chips
    out["model_flops_ratio"] = (out["model_flops"] / total_hlo
                                if total_hlo else None)
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             overrides: dict | None = None,
             target: str = "tpu_v5e") -> dict:
    multi = mesh_kind == "multi"
    t0 = time.time()
    record: dict
    if not cell_applicable(arch, shape_name):
        record = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "status": "skipped",
                  "reason": "full-attention arch: long_500k inapplicable "
                            "(DESIGN.md §Arch-applicability)"}
    else:
        try:
            compiled, lowered, meta = lower_cell(arch, shape_name, multi,
                                                 overrides)
            record = analyse(compiled, lowered, meta, time.time() - t0,
                             target=target)
            record["mesh"] = mesh_kind
            record["status"] = "ok"
        except Exception as e:
            record = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                      "status": "error", "error": f"{type(e).__name__}: {e}",
                      "traceback": traceback.format_exc()[-4000:]}
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_kind}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(record, f, indent=2, default=str)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--target", choices=sorted(TARGET_ROOFLINES),
                    default="tpu_v5e",
                    help="modeled machine for the roofline terms (the HLO "
                         "itself is target-independent); nightly sweeps "
                         "both, each into its own --out dir")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    if not args.all and not (args.arch and args.shape):
        ap.error("either --all or both --arch and --shape are required")

    cells = ([(a, s) for a in ARCHS for s in SHAPES] if args.all
             else [(args.arch, args.shape)])
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    failures = 0
    for arch, shape in cells:
        for mesh_kind in meshes:
            path = os.path.join(args.out,
                                f"{arch}__{shape}__{mesh_kind}.json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    prev = json.load(f)
                if prev.get("status") in ("ok", "skipped"):
                    print(f"[skip] {arch} {shape} {mesh_kind} (cached)")
                    continue
            t0 = time.time()
            rec = run_cell(arch, shape, mesh_kind, args.out,
                           target=args.target)
            dt = time.time() - t0
            status = rec["status"]
            extra = ""
            if status == "ok":
                r = rec["roofline"]
                extra = (f"flops={rec['hlo_flops']:.3g} "
                         f"coll={rec['collectives']['total_bytes']:.3g}B "
                         f"dom={r['dominant']}")
            elif status == "error":
                extra = rec["error"][:160]
                failures += 1
            print(f"[{status}] {arch} {shape} {mesh_kind} ({dt:.0f}s) {extra}",
                  flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
