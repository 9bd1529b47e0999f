"""prefill_lowerings_per_call: JAX's MLIR lowerings (``jax.monitoring``)
counted under the program's ``generate.prefill`` spans in the window, per
span."""
from bench.span_readers import counter_under, named, window_records


def read(r):
    recs = window_records(r)
    n = len(named(recs, "generate.prefill"))
    if not n:
        return None
    return counter_under(recs, "generate.prefill", "lowerings") / n
