"""ttft_p50_ms: the median (nearest rank) of the same times as
``ttft_p95_ms``: a steadier statistic beside it, whose tail swings with
the host."""
from bench.readers import ttft_ms


def read(r):
    return ttft_ms(r, 0.5)
