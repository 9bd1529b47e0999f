"""mla_decode_roofline: the least time of the decode steps' latent
attention in the traced batches (bench/flops_mla.py: per step and layer,
the larger of the absorbed scores and weighted sum over pos + 1 positions
at the peak bf16 rate, and the latent cache's bf16 bytes over the HBM
bandwidth), over the device self time of the instructions under the
``mla.attend`` scope in the decode program's runs (the driver gives each
operation of the trace to the program run that holds it)."""
from bench.flops_mla import attend_roofline_s
from bench.readers import traced_units


def read(r):
    units = traced_units(r, "units", "due")
    c = r.counts
    device_s = c.get("programs", {}).get("decode", {}).get("mla.attend", 0.0)
    if not units or c["new_tokens"] < 2 or device_s <= 0:
        return None
    bound = attend_roofline_s(r.cfg, c["batch"], c["prompt_len"], c["new_tokens"], r.peak)
    return 100.0 * len(units) * bound / device_s
