"""output_tok_s: every token generated by the batches started in the
window, over the wall time from the first batch's issue to the last
batch's return (host clock)."""
from bench.readers import window_span


def read(r):
    units = r.counts.get("units", [])
    if not units or r.counts["new_tokens"] < 2:
        return None
    return sum(u["tokens"] for u in units) / window_span(units, "due")
