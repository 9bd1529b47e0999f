"""Arithmetic shared by the metric readers of the program's own spans and
compile counters (``repro.runtime.spans``, recorded in this process).

A program without that module records no spans: these helpers then find
nothing, and the readers return None.
"""
from __future__ import annotations


def _spans():
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans


def window_records(r) -> list:
    """The program's spans inside the run's window (from its first unit's
    due time to its last unit's end, as ``readers.compile_share``)."""
    spans, units = _spans(), r.counts.get("units", [])
    if spans is None or not units:
        return []
    return spans.records(units[0]["due"], units[-1]["end"])


def all_records() -> list:
    """Every span the program kept in this process."""
    spans = _spans()
    return [] if spans is None else spans.records()


def named(recs, name: str) -> list:
    return [s for s in recs if s.name == name]


def counter_under(recs, name: str, counter: str) -> float:
    """``counter`` summed over the spans named ``name`` and their subtrees."""
    spans = named(recs, name)
    if not spans:
        return 0
    inc = _spans().inclusive(recs)
    return sum(inc[s.id][counter] for s in spans)


def decode_steps(recs) -> int:
    """Decode steps of the ``generate`` calls among ``recs``."""
    return sum(s.attrs["steps"] for s in named(recs, "generate.decode"))
