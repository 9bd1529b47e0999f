"""mfu.prefill: the model operations of each traced batch's prefill
(bench/flops.py, causal attention counted at half), over that batch's time
to first token, over the chip's peak bf16 rate."""
from bench.flops import prefill_flops
from bench.readers import traced_units


def read(r):
    units = traced_units(r, "units", "due")
    c = r.counts
    if not units or c["new_tokens"] != 1:
        return None
    flops = len(units) * prefill_flops(r.cfg, c["batch"], c["prompt_len"])
    ttft = sum(u["end"] - u["due"] for u in units)
    return 100.0 * flops / ttft / r.peak["bf16_flops_per_s"]
