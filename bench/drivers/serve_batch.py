"""Closed loop of fixed-size batches through the program's serving entry
point, ``repro.launch.serve.generate``.

Traffic (the cell's ``traffic``): ``batch`` prompts of ``prompt_len``
tokens each, ``new_tokens`` greedy tokens per prompt; the next batch is
issued when the last one has returned.  Batch ``i``'s prompts are drawn
from the seed and ``i``, so every seed does the same work on other tokens.

Correctness: ``check_requests`` finished requests, drawn from the seed by
reservoir sampling over every request of the window (their served tokens
and the logits they were chosen from are kept), are run through the f32
reference teacher-forced on prompt + served tokens, after the window, and
read as ``reading`` says.  A cell compares those numbers its ``limits``
name, and counts tokens outside the vocabulary.
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

#: device time is attributed to the span of the call that issued it
ATTRIBUTE = ("bench.prefill", "bench.decode")


def _spanned(fn, name):
    def call(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return call


def setup(ctx):
    from repro.launch.serve import generate
    from repro.models import build_model

    cfg, tr, ref = ctx.model_cfg, ctx.traffic, ctx.reference
    model = build_model(cfg)
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                       ref.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    if jax.tree.structure(want) != jax.tree.structure(got) or jax.tree.leaves(
            jax.tree.map(lambda a, b: a.shape != b.shape or a.dtype != b.dtype,
                         want, got)).count(True):
        raise ValueError("the reference's parameter layout is not the program's")

    k_w, k_p = jax.random.split(ctx.key)
    params = jax.jit(ref.init_params, static_argnums=1)(k_w, cfg)
    B, T, V = tr["batch"], tr["prompt_len"], cfg.vocab_size
    prompts = jax.jit(lambda i: jax.random.randint(
        jax.random.fold_in(k_p, i), (B, T), 0, V, dtype=jnp.int32))

    model.prefill = _spanned(model.prefill, "bench.prefill")
    model.decode_step = _spanned(model.decode_step, "bench.decode")
    state = SimpleNamespace(model=model, params=params, prompts=prompts,
                            generate=generate, tokens={}, kept=[], seen=0,
                            rng=np.random.default_rng([ctx.seed % 2 ** 64, 7]))
    # warm-up: the cell's one shape, on prompts no batch of the window uses
    toks, _ = generate(model, params, {"tokens": prompts(2 ** 31 - 1)},
                       tr["new_tokens"])
    np.asarray(toks)
    return state


def serve(ctx, state, until):
    """Issue batches while ``until(i, elapsed)`` holds; return the units."""
    tr = ctx.traffic
    units, i, t_start = [], 0, time.perf_counter()
    while until(i, time.perf_counter() - t_start):
        ctx.tracer.boundary()
        due = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.batch"):
            toks, logits = state.generate(state.model, state.params,
                                          {"tokens": state.prompts(i)},
                                          tr["new_tokens"], greedy=tr["greedy"])
            toks = np.asarray(toks)
        end = time.perf_counter()
        state.tokens[i] = toks
        keep(ctx, state, i, toks, logits)
        units.append({"index": i, "due": due, "end": end,
                      "requests": toks.shape[0], "tokens": int(toks.size)})
        i += 1
    ctx.tracer.boundary()
    return units


def keep(ctx, state, i, toks, logits):
    """Reservoir sampling of ``check_requests`` requests: each finished
    request is kept with the same chance, drawn from the seed."""
    k = ctx.traffic["check_requests"]
    for r in range(toks.shape[0]):
        j = state.seen if state.seen < k else int(state.rng.integers(0, state.seen + 1))
        if j < k:
            entry = (i, r, toks[r], logits[r])
            if j < len(state.kept):
                state.kept[j] = entry
            else:
                state.kept.append(entry)
        state.seen += 1


def window(ctx, state):
    units = serve(ctx, state, lambda i, elapsed: elapsed < ctx.seconds)
    tr = ctx.traffic
    return {"units": units, "attempted": sum(u["requests"] for u in units),
            "failed": 0, "batch": tr["batch"], "prompt_len": tr["prompt_len"],
            "new_tokens": tr["new_tokens"]}


def release(state):
    """Drop what the program made; the weights stay for the reference."""
    state.model = state.generate = None


def reading(ctx, state, control: bool = False) -> dict:
    """The compared numbers, by name, over the kept requests and their
    served positions (with ``control``, the float8 reference stands in for
    the program: its first token and its logits):

    - ``served_logit_gap``: the widest gap of the reference's best logit
      over its logit of the served token;
    - ``logit_rel_error``: the largest |served logit - reference logit|
      over the reference's largest |logit|;
    - ``served_token_excess``: the largest amount, relative to the
      reference's largest |logit|, by which that gap exceeds twice the
      served logits' own largest error at the position.  A token chosen
      greedily from the served logits never exceeds it, whatever their
      precision (its reference logit lies within one error of its served
      logit, which is the served maximum, which lies within one error of
      the reference's best), so this is at most 0 unless a token was
      altered after it was chosen.
    """
    cfg, ref = ctx.model_cfg, ctx.reference
    T = ctx.traffic["prompt_len"]
    gap = err = excess = -np.inf
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
    return {"served_logit_gap": gap, "logit_rel_error": err, "served_token_excess": excess}


def minimal(ctx, state):
    """The fewest batches that finish as many requests as a run compares."""
    need = ctx.traffic["check_requests"]
    return serve(ctx, state, lambda i, elapsed: i * ctx.traffic["batch"] < need)


def check(ctx, state, counts):
    """Tokens outside the vocabulary (limit 0), and each compared number the
    cell's file gives a limit for."""
    V = ctx.model_cfg.vocab_size
    bad = sum(int(((t < 0) | (t >= V)).sum()) for t in state.tokens.values())
    limits = ctx.traffic["limits"]
    return [{"name": "tokens_out_of_vocab", "value": bad, "limit": 0}] + [
        {"name": k, "value": v, "limit": limits[k]}
        for k, v in reading(ctx, state).items() if k in limits]
