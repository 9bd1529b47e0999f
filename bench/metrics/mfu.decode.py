"""mfu.decode: the model operations of the batches in the traced window
(bench/flops.py: prefill with causal attention counted at half, then one
decode step per further token, each attending its positions), over the
traced window's wall time, over the chip's peak bf16 rate."""
from bench.flops import generate_flops
from bench.readers import traced_units


def read(r):
    units = traced_units(r, "units", "due")
    c = r.counts
    if not units or c["new_tokens"] < 2:
        return None
    flops = len(units) * generate_flops(r.cfg, c["batch"], c["prompt_len"], c["new_tokens"])
    return 100.0 * flops / (r.tracer.t1 - r.tracer.t0) / r.peak["bf16_flops_per_s"]
