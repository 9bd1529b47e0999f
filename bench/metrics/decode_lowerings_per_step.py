"""decode_lowerings_per_step: JAX's MLIR lowerings (``jax.monitoring``)
counted under the program's ``generate.decode`` spans in the window, over
their total ``steps``.  An eager decode step lowers its layer scan on
every call; a jitted one lowers nothing in steady state."""
from bench.span_readers import counter_under, decode_steps, window_records


def read(r):
    recs = window_records(r)
    steps = decode_steps(recs)
    if not steps:
        return None
    return counter_under(recs, "generate.decode", "lowerings") / steps
