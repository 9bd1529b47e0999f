"""Round-robin over the configuration's GEMM shapes through the program's
ISA-mapped Pallas kernel, ``repro.kernels.gemm.gemm``, with the tile that
``repro.kernels.ops.plan_gemm`` plans (no tuning cache is read).

A round calls the kernel once per shape.  One compiled program runs the
traffic's ``rounds_per_program`` rounds, as a model's step issues many
kernels at once: one dispatch of a program costs the host about as much
as the device's work of three rounds (PERF.md), so a program of one round
would time the host.  An optimization barrier in front of each round keeps
the compiler from merging the rounds, which read the same operands, and
the program returns an element of each round's outputs, so none is dead
code.  The loop keeps one program in flight: it issues program
``i + 1`` and then blocks on program ``i``'s outputs (its last round's),
so the host's dispatch overlaps the device's work and the wall clock
follows the kernels and their wrapper.

Set-up plans the tiles (timed: ``isam_plan_s``), makes the operands from
the seed on the device, compiles the program and the same program with
XLA's own dot, and prints the time of a round of each on standard error,
as context.

Correctness: the outputs of ``check_programs`` programs of the window,
drawn from the seed by reservoir sampling, against the f32 HIGHEST
reference: the largest ``max |C - C_ref| / max |C_ref|`` over shapes and
programs.
"""
from __future__ import annotations

import functools
import re
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

ATTRIBUTE = ()
CONTEXT_PROGRAMS = 50


def _program(fns, rounds):
    """One jitted program of ``rounds`` rounds of ``fns`` over the operands.
    It returns the last round's outputs, and the first element of every
    output of every round, so that no round's calls are dead code."""
    def run(operands):
        corners = []
        for _ in range(rounds):
            operands = jax.lax.optimization_barrier(operands)
            outs = [f(a, b) for f, (a, b) in zip(fns, operands)]
            corners += [o[0, 0] for o in outs]
        return outs, jnp.stack(corners)
    return jax.jit(run)


def kernel_program(tiles, rounds, interpret):
    """The program of ``rounds`` rounds of the kernel at the planned tiles."""
    from repro.kernels import gemm as kernels
    return _program([functools.partial(kernels.gemm, block=tile, interpret=interpret)
                     for tile in tiles], rounds)


def _xla_dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def kernel_op_names(program, operands) -> list:
    """Names of the Pallas custom calls in the compiled program, one per
    kernel call: the names its operations carry in the device trace."""
    text = program.lower(operands).compile().as_text()
    return re.findall(r"%([\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call", text)


def _time_programs(fn, operands, n):
    jax.block_until_ready(fn(operands))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(operands)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n


def setup(ctx):
    from repro.kernels.ops import plan_gemm
    doc = ctx.cell["config_file"]
    shapes = [tuple(s) for s in doc["shapes"]]
    rounds = ctx.traffic["rounds_per_program"]
    t = time.perf_counter()
    tiles = [plan_gemm(m, n, k, use_cache=False)[0] for m, n, k in shapes]
    plan_s = time.perf_counter() - t
    program = kernel_program(tiles, rounds, ctx.interpret)
    operands = jax.jit(ctx.reference.make_operands, static_argnums=(1, 2))(
        ctx.key, tuple(shapes), jnp.dtype(doc["dtype"]))
    xla = _program([_xla_dot] * len(shapes), rounds)
    ops = kernel_op_names(program, operands)
    kern_s = _time_programs(program, operands, CONTEXT_PROGRAMS)
    xla_s = _time_programs(xla, operands, CONTEXT_PROGRAMS)
    for (m, n, k), tile in zip(shapes, tiles):
        print(f"[context] gemm {m}x{n}x{k} tile {tile}", file=sys.stderr)
    print(f"[context] {len(ops)} Pallas calls in the program; one round, over"
          f" {CONTEXT_PROGRAMS} programs of {rounds} in set-up (not a metric):"
          f" ISA-mapped Pallas {kern_s / rounds * 1e6:.1f} us,"
          f" XLA jnp.dot {xla_s / rounds * 1e6:.1f} us", file=sys.stderr, flush=True)
    return SimpleNamespace(shapes=shapes, tiles=tiles, rounds=rounds, program=program,
                           operands=operands, plan_s=plan_s, kernel_ops=set(ops), kept=[])


def window(ctx, state):
    keep = ctx.traffic["check_programs"]
    rng = np.random.default_rng([ctx.seed % 2 ** 64, 11])
    program, operands = state.program, state.operands
    units, i, t_start = [], 0, time.perf_counter()
    ctx.tracer.boundary()
    start, outs = time.perf_counter(), program(operands)
    while True:
        more = time.perf_counter() - t_start < ctx.seconds
        if more:
            ctx.tracer.boundary()
            nxt_start, nxt = time.perf_counter(), program(operands)
        with jax.profiler.TraceAnnotation("bench.program"):
            jax.block_until_ready(outs)
        units.append((start, time.perf_counter()))
        # reservoir sample of ``keep`` programs' outputs
        if i < keep:
            state.kept.append(outs[0])
        else:
            j = int(rng.integers(0, i + 1))
            if j < keep:
                state.kept[j] = outs[0]
        i += 1
        if not more:
            break
        start, outs = nxt_start, nxt
    ctx.tracer.boundary()
    return {"rounds": [{"start": s, "end": e, "rounds": state.rounds} for s, e in units],
            "shapes": state.shapes, "tiles": state.tiles,
            "attempted": i * state.rounds * len(state.shapes), "failed": 0,
            "plan_s": state.plan_s, "kernel_ops": sorted(state.kernel_ops)}


def release(state):
    state.program = None


def errors(ctx, state, control: bool = False) -> list:
    """Relative error of each kept output (with ``control``: of the float8
    reference in the kernel's place)."""
    ref = ctx.reference
    out = []
    for idx, (a, b) in enumerate(state.operands):
        want = ref.matmul(a, b)
        scale = float(jnp.max(jnp.abs(want)))
        if control:
            got = [ref.matmul(a, b, quantize=True).astype(a.dtype)]
        else:
            got = [outs[idx] for outs in state.kept]
        for g in got:
            out.append(float(jnp.max(jnp.abs(g.astype(jnp.float32) - want))) / scale)
    return out


def minimal(ctx, state):
    """As many programs as a run keeps for the check."""
    for _ in range(ctx.traffic["check_programs"]):
        state.kept.append(jax.block_until_ready(state.program(state.operands))[0])


def reading(ctx, state, control: bool = False) -> dict:
    """The compared number, by name: the largest relative error."""
    return {"gemm_rel_error": max(errors(ctx, state, control))}


def check(ctx, state, counts):
    limits = ctx.traffic["limits"]
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in reading(ctx, state).items()]
