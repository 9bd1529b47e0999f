"""hbm_roofline.mla: the bytes the decode steps of the traced batches need
in bf16 (bench/flops_mla.py: every weight a step uses read once, the held
experts it is expected to touch, the latent cache read up to the position
and its new row written), over the device time of the decode program's
runs (the driver reads them from the trace), over the chip's HBM
bandwidth."""
from bench.flops_mla import generate_decode_bytes
from bench.readers import traced_units


def read(r):
    units = traced_units(r, "units", "due")
    c = r.counts
    device_s = c.get("programs", {}).get("decode", {}).get("s", 0.0)
    if not units or c["new_tokens"] < 2 or device_s <= 0:
        return None
    nbytes = len(units) * generate_decode_bytes(r.cfg, c["batch"], c["prompt_len"],
                                                c["new_tokens"])
    return 100.0 * nbytes / device_s / r.peak["hbm_bytes_per_s"]
