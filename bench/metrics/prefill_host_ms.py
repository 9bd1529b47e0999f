"""prefill_host_ms: the median duration, in ms, of the program's
``generate.prefill`` spans in the window: host time to trace, lower, load
and dispatch the prefill (its device work runs on after the span ends)."""
import statistics

from bench.span_readers import named, window_records


def read(r):
    spans = named(window_records(r), "generate.prefill")
    if not spans:
        return None
    return 1e3 * statistics.median(s.seconds for s in spans)
