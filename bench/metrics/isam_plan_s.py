"""isam_plan_s: host seconds that set-up spent planning the GEMM tiles
through the compilation driver (kernels/ops.plan_gemm, no tuning cache)."""


def read(r):
    return r.counts.get("plan_s")
