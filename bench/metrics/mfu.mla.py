"""mfu.mla: the model operations of the batches in the traced window
(bench/flops_mla.py: prefill in the per-head form with causal attention
counted at half, then one absorbed decode step per further token, each
attending its positions; routed experts at the expected assignments to the
held ones), over the traced window's wall time, over the chip's peak bf16
rate."""
from bench.flops_mla import generate_flops
from bench.readers import traced_units


def read(r):
    units = traced_units(r, "units", "due")
    c = r.counts
    if not units or c["new_tokens"] < 2:
        return None
    flops = len(units) * generate_flops(r.cfg, c["batch"], c["prompt_len"], c["new_tokens"])
    return 100.0 * flops / (r.tracer.t1 - r.tracer.t0) / r.peak["bf16_flops_per_s"]
