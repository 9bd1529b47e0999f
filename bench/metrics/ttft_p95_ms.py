"""ttft_p95_ms: 95th percentile (nearest rank) over every request of the
window of the time from its due time (its batch was issued) to its first
token (host clock)."""
from bench.readers import ttft_ms


def read(r):
    return ttft_ms(r, 0.95)
