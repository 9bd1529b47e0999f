"""isam_schedule_s: host seconds in the ISA compiler's schedule pass (the
program's ``isam.schedule`` spans) over the whole process: set-up plans the
GEMM tiles, and the window runs no compiler."""
from bench.span_readers import all_records, named


def read(r):
    spans = named(all_records(), "isam.schedule")
    if not spans:
        return None
    return sum(s.seconds for s in spans)
