"""gemm_roofline: over the kernel calls of the traced programs, the sum of
each call's least time on the chip (the larger of 2mnk over the peak bf16
rate and its bf16 operand and output bytes over the HBM bandwidth,
bench/flops.py), over the Pallas kernel's device time from the trace."""
from bench.flops import gemm_bytes, gemm_flops, roofline_s
from bench.readers import gemm_kernel_s, traced_units


def read(r):
    units = traced_units(r, "rounds", "start")
    kern = gemm_kernel_s(r)
    if not units or kern is None:
        return None
    bound = sum(roofline_s(gemm_flops(m, n, k), gemm_bytes(m, n, k), r.peak)
                for m, n, k in r.counts["shapes"])
    return 100.0 * bound * sum(u["rounds"] for u in units) / kern
