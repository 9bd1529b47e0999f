"""gemm_overhead_share: device time of the traced window outside the Pallas
kernel's operations (the wrapper's pad, crop and cast), over the device's
busy time."""
from bench.readers import gemm_kernel_s


def read(r):
    kern = gemm_kernel_s(r)
    if kern is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - kern / r.trace.n_devices / r.trace.busy_s)
