"""moe_expert_roofline: the least time of the routed experts' work in the
traced batches (bench/flops_mla.py: per MoE layer, one call over the
prompt tokens and one per decode step; each the larger of its operations
over the peak bf16 rate and its bytes, the held experts it touches and the
routed rows, over the HBM bandwidth), over the device self time of the
instructions under the ``moe.experts`` scope in the runs of the compiled
prefill and decode programs (the driver gives each operation of the trace
to the program run that holds it)."""
from bench.flops_mla import expert_roofline_s
from bench.readers import traced_units


def read(r):
    units = traced_units(r, "units", "due")
    progs = r.counts.get("programs", {})
    device_s = sum(p["moe.experts"] for p in progs.values())
    if not units or device_s <= 0:
        return None
    c = r.counts
    bound = expert_roofline_s(r.cfg, c["batch"], c["prompt_len"], c["new_tokens"], r.peak)
    return 100.0 * len(units) * bound / device_s
