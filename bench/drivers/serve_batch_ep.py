"""``serve_batch`` for a configuration whose MoE layers hold one chip's
share of the experts (expert parallelism, run without its exchange).

Set-up applies the configuration file's share to the program's
configuration: ``n_routed_experts`` experts held, from
``first_held_expert``, of the router's published count.  On a traced run
it reads, from the model's compiled prefill and decode programs (those the
run dispatches, ``DecoderLM.lower_serving``), each program's module name
and the labels (name and result type, as ``trace_reduce.op_label`` reads
them from the device trace) of its instructions under the named scopes
``moe.experts`` and ``mla.attend``.  After the window it gives each
operation of the trace to the program whose run on the device holds it
(the device plane's ``XLA Modules`` line), so that a label both programs
use is counted in each where it ran, and nothing is left out.

The check adds ``logit_rel_error_p50`` to ``serve_batch``'s numbers
(``reading``).  The traffic, the window and the control are
``serve_batch``'s.
"""
from __future__ import annotations

import bisect
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, trace_reduce

_base = harness.load_module("drivers", "serve_batch", Path(__file__).resolve().parents[1])
ATTRIBUTE = _base.ATTRIBUTE
window_units = _base.window
release, minimal = _base.release, _base.minimal

#: the named scopes whose device time the cell's metrics read
SCOPES = ("moe.experts", "mla.attend")
MODULES_LINE = "XLA Modules"
INSTR = re.compile(r"^%?[\w.\-]+ = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')
#: op_name prefix -> scope, for instructions whose op_name lost the
#: caller's scopes: on the TPU, ``jax.lax.ragged_dot`` lowers to a Mosaic
#: kernel named ``ragged-dot-none`` (and ``ragged-dot-metadata`` for its
#: group offsets), and the program calls it only under ``moe.experts``
UNSCOPED = {"ragged-dot": "moe.experts"}


def scope_labels(hlo_text: str) -> dict:
    """{scope: labels of the instructions whose op_name holds that scope}
    of optimized HLO text."""
    scoped = {}
    for line in hlo_text.splitlines():
        text = line.strip().removeprefix("ROOT ")
        if not INSTR.match(text):
            continue
        op = OP_NAME.search(text)
        name = op.group(1) if op else ""
        scopes = [s for s in name.split("/") if s]
        scopes += [s for prefix, s in UNSCOPED.items() if name.startswith(prefix)]
        for scope in scopes:
            scoped.setdefault(scope, set()).add(trace_reduce.op_label(text)[1])
    return scoped


def programs(model, params, traffic) -> dict:
    """{"prefill" | "decode": {"module": its HLO module name, scope: sorted
    labels}} of the programs that serve the cell's batches."""
    B, T, n = traffic["batch"], traffic["prompt_len"], traffic["new_tokens"]
    batch = {"tokens": jax.ShapeDtypeStruct((B, T), jnp.int32)}
    out = {}
    for role, lowered in zip(("prefill", "decode"), model.lower_serving(params, batch, T + n)):
        text = lowered.compile().as_text()
        scoped = scope_labels(text)
        out[role] = {"module": text.split(",", 1)[0].removeprefix("HloModule ")}
        out[role].update({s: sorted(scoped.get(s, ())) for s in SCOPES})
        print(f"[context] {role} ({out[role]['module']}): "
              + ", ".join(f"{s} {len(out[role][s])} instructions" for s in SCOPES),
              file=sys.stderr, flush=True)
    return out


def program_times(profile, progs: dict) -> dict:
    """{role: {"runs", "s": device seconds of its runs, scope: device self
    seconds of its scope's instructions}} in the traced window: each
    operation belongs to the run of the module (program) that holds its
    start on its device."""
    spans = trace_reduce.host_spans(profile)
    w0, w1 = next((s, e) for n, s, e in spans if n == trace_reduce.WINDOW_SPAN)
    role_of = {p["module"]: role for role, p in progs.items()}
    out = {role: {"runs": 0, "s": 0.0, **{s: 0.0 for s in SCOPES}} for role in progs}
    for plane in profile.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        runs, ops = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                runs.extend((ev.name.split("(")[0], ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events)
            elif line.name == trace_reduce.OPS_LINE:
                ops.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events)
        runs = sorted(((m, max(s, w0), min(e, w1)) for m, s, e in runs
                       if m in role_of and e > w0 and s < w1), key=lambda r: r[1])
        for m, s, e in runs:
            out[role_of[m]]["runs"] += 1
            out[role_of[m]]["s"] += (e - s) * 1e-9
        starts = [s for _, s, _ in runs]
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        for (n, s, _), own in zip(clipped, trace_reduce.self_times(clipped)):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][2]:
                continue
            role = role_of[runs[i][0]]
            label = trace_reduce.op_label(n)[1]
            for scope in SCOPES:
                if label in progs[role]["labels"][scope]:
                    out[role][scope] += own * 1e-9
    return out


def setup(ctx):
    doc = ctx.cell["config_file"]
    ctx.model_cfg = ctx.model_cfg.scaled(held_experts=doc["n_routed_experts"],
                                         first_held_expert=doc["first_held_expert"])
    state = _base.setup(ctx)
    state.programs = {}
    if ctx.tracer.enabled:          # only a traced run reads them
        t = time.perf_counter()
        state.programs = programs(state.model, state.params, ctx.traffic)
        print(f"[context] reading the compiled programs took"
              f" {time.perf_counter() - t:.1f} s", file=sys.stderr, flush=True)
    return state


def window(ctx, state):
    counts = window_units(ctx, state)
    tracer = ctx.tracer
    counts["programs"] = {}
    if state.programs and tracer.dir is not None:
        tracer.stop()
        files = sorted(Path(tracer.dir.name).rglob("*.xplane.pb"))
        if files:
            progs = {role: {"module": p["module"], "labels": {s: set(p[s]) for s in SCOPES}}
                     for role, p in state.programs.items()}
            counts["programs"] = program_times(trace_reduce.load(files[-1]), progs)
            for role, t in counts["programs"].items():
                print(f"[context] traced {role}: {t['runs']} runs, {t['s']:.4f} s on the device; "
                      + ", ".join(f"{s} {t[s]:.4f} s" for s in SCOPES),
                      file=sys.stderr, flush=True)
    return counts


def reading(ctx, state, control: bool = False) -> dict:
    """``serve_batch``'s compared numbers over the kept requests' served
    positions, and ``logit_rel_error_p50``: the median over those positions
    of |served logit - reference logit| (largest over the vocabulary) over
    the reference's largest |logit|.  A top-k choice near a tie that bf16
    and f32 settle apart changes the logits at a few positions, which
    ``logit_rel_error``, the largest, reads; the median reads the bulk, where
    a fault that reaches every token shows."""
    cfg, ref = ctx.model_cfg, ctx.reference
    T = ctx.traffic["prompt_len"]
    gap = err = excess = -np.inf
    rel = []
    for i, r, served, logits in state.kept:
        seq = jnp.concatenate([state.prompts(i)[r], jnp.asarray(served[:-1])])
        at = np.arange(T - 1, T - 1 + len(served))
        want = np.asarray(ref.logits(state.params, seq, cfg, at), np.float64)
        got = np.asarray(logits, np.float64)
        if control:
            got = np.asarray(ref.logits(state.params, seq, cfg, at, quantize=True), np.float64)
        pick = got.argmax(-1) if control else served
        gaps = want.max(-1) - want[np.arange(len(pick)), pick]
        errs = np.abs(got - want).max(-1)
        scale = np.abs(want).max(-1)
        gap = max(gap, float(gaps.max()))
        err = max(err, float((errs / scale).max()))
        excess = max(excess, float(((gaps - 2 * errs) / scale).max()))
        rel.append(errs / scale)
    return {"served_logit_gap": gap, "logit_rel_error": err, "served_token_excess": excess,
            "logit_rel_error_p50": float(np.median(np.concatenate(rel)))}


def check(ctx, state, counts):
    """Tokens outside the vocabulary (limit 0), and each number of
    ``reading`` that the cell's file gives a limit for."""
    V = ctx.model_cfg.vocab_size
    bad = sum(int(((t < 0) | (t >= V)).sum()) for t in state.tokens.values())
    limits = ctx.traffic["limits"]
    return [{"name": "tokens_out_of_vocab", "value": bad, "limit": 0}] + [
        {"name": k, "value": v, "limit": limits[k]}
        for k, v in reading(ctx, state).items() if k in limits]
