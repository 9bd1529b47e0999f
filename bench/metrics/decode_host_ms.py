"""decode_host_ms: host milliseconds of the decode loop per decode step:
the total duration of the program's ``generate.decode`` spans in the
window over their total ``steps``.  It covers the loop as a whole, so it
reads the same whether the loop runs step by step or as one program."""
from bench.span_readers import decode_steps, named, window_records


def read(r):
    recs = window_records(r)
    steps = decode_steps(recs)
    if not steps:
        return None
    return 1e3 * sum(s.seconds for s in named(recs, "generate.decode")) / steps
