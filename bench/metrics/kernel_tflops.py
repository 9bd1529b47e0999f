"""kernel_tflops: the operations of every kernel call of the programs
started in the window (bench/flops.py), over the wall time from the first
program's start to the last one's end (host clock)."""
from bench.flops import gemm_flops
from bench.readers import window_span


def read(r):
    units = r.counts.get("rounds", [])
    if not units:
        return None
    per_round = sum(gemm_flops(m, n, k) for m, n, k in r.counts["shapes"])
    return per_round * sum(u["rounds"] for u in units) / window_span(units, "start") / 1e12
