"""mfu.gemm: the operations of the traced programs (2mnk per call), over
the traced window's wall time, over the chip's peak bf16 rate: the whole
program's share of the peak, beside the kernel's roofline share."""
from bench.flops import gemm_flops
from bench.readers import traced_units


def read(r):
    units = traced_units(r, "rounds", "start")
    if not units:
        return None
    flops = sum(u["rounds"] for u in units) * sum(gemm_flops(m, n, k)
                                                  for m, n, k in r.counts["shapes"])
    return 100.0 * flops / (r.tracer.t1 - r.tracer.t0) / r.peak["bf16_flops_per_s"]
