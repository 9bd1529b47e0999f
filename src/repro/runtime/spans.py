"""Host spans and JAX's compile counters, kept in memory.

``span(name, **attrs)`` times one piece of host-side work.  It enters a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows the span on its host plane, on the clock of the device's operations;
with no profiler session open that costs next to nothing.  On exit the span
appends one ``Record`` to a bounded in-memory deque.  Spans nest per
thread: every span names its parent and its root, and all spans under one
root share the root's id (one ``generate`` call, one compile).

JAX's own compile events (``jax.monitoring``) are counted into the
innermost span open on the thread that reports them: jaxpr traces, MLIR
lowerings, backend compiles (a compile, or a load from the persistent
compilation cache) and persistent-cache hits and misses.

Readers: ``records(t0, t1)`` for the spans of a window on
``time.perf_counter``, ``inclusive`` for counters summed over each span's
subtree, and ``summarize`` for a table by span name.

Recording is always on, so it has to stay cheap.  Spans go only at
host-side boundaries that run once per step or less often; never inside
code that JAX traces (the Python there runs once per trace, not per call:
use ``jax.named_scope``, which labels the compiled operations instead);
never per element.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

#: finished spans kept; the oldest are dropped first
MAX_RECORDS = 1 << 16

#: ``jax.monitoring`` duration events -> counter (a count, and seconds
#: under ``<counter>_s``)
DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
    "/jax/core/compile/backend_compile_duration": "compiles",
}
#: ``jax.monitoring`` events -> counter (a count)
COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


@dataclass
class Record:
    """One span.  ``counters`` holds what JAX reported while this span was
    the innermost open one on its thread (its children keep their own)."""

    id: int
    parent: int | None
    root: int
    name: str
    start: float
    end: float | None = None
    attrs: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextmanager
def span(name: str, **attrs):
    """Time the enclosed host work as span ``name``; yields its ``Record``
    (``end`` is set on exit)."""
    stack = _stack()
    parent = stack[-1] if stack else None
    rid = next(_ids)
    rec = Record(rid, parent.id if parent else None,
                 parent.root if parent else rid, name, time.perf_counter(),
                 attrs=attrs)
    stack.append(rec)
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield rec
    finally:
        rec.end = time.perf_counter()
        stack.pop()
        _records.append(rec)


def _count(name: str, seconds: float | None = None) -> None:
    stack = getattr(_local, "stack", None)
    if not stack:
        return
    c = stack[-1].counters
    c[name] = c.get(name, 0) + 1
    if seconds is not None:
        c[name + "_s"] = c.get(name + "_s", 0.0) + seconds


def _on_duration(event: str, duration: float, **kw) -> None:
    name = DURATION_EVENTS.get(event)
    if name is not None:
        _count(name, duration)


def _on_event(event: str, **kw) -> None:
    name = COUNT_EVENTS.get(event)
    if name is not None:
        _count(name)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def records(t0: float | None = None, t1: float | None = None) -> list:
    """Finished spans that started at or after ``t0`` and ended at or
    before ``t1`` (``time.perf_counter``; None: no limit), in the order
    they ended, so a span comes after its children."""
    return [r for r in list(_records)
            if (t0 is None or r.start >= t0) and (t1 is None or r.end <= t1)]


def inclusive(recs) -> dict:
    """{span id: ``collections.Counter`` of its counters summed over it and
    its descendants among ``recs``}; ``recs`` in the order they ended."""
    out = {}
    for r in recs:
        c = out.setdefault(r.id, collections.Counter())
        c.update(r.counters)
        if r.parent is not None:
            out.setdefault(r.parent, collections.Counter()).update(c)
    return out


def summarize(recs) -> dict:
    """{span name: {count, seconds, self_s, counters}} over ``recs``:
    ``seconds`` the spans' total duration, ``self_s`` that less the time of
    their children among ``recs``, ``counters`` summed over each span's
    subtree (``inclusive``)."""
    child_s = collections.Counter()
    for r in recs:
        if r.parent is not None:
            child_s[r.parent] += r.seconds
    inc = inclusive(recs)
    out = {}
    for r in recs:
        s = out.setdefault(r.name, {"count": 0, "seconds": 0.0, "self_s": 0.0,
                                    "counters": collections.Counter()})
        s["count"] += 1
        s["seconds"] += r.seconds
        s["self_s"] += r.seconds - child_s[r.id]
        s["counters"].update(inc[r.id])
    for s in out.values():
        s["counters"] = dict(s["counters"])
    return out
