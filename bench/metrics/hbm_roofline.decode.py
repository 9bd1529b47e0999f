"""hbm_roofline.decode: the bytes the decode steps of the traced batches
need in bf16 (bench/flops.py: weights once, K/V read up to the position,
new K/V written), over the device-busy time issued inside the benchmark's
``bench.decode`` spans, over the chip's HBM bandwidth."""
from bench.flops import generate_decode_bytes
from bench.readers import traced_units


def read(r):
    units = traced_units(r, "units", "due")
    c = r.counts
    busy = r.trace.span_busy_s.get("bench.decode", 0.0) if r.trace else 0.0
    if not units or c["new_tokens"] < 2 or busy <= 0:
        return None
    nbytes = len(units) * generate_decode_bytes(r.cfg, c["batch"], c["prompt_len"],
                                                c["new_tokens"])
    return 100.0 * nbytes / busy / r.peak["hbm_bytes_per_s"]
