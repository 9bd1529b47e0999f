"""Arithmetic shared by the metric readers in ``metrics/``.

A reader gets the run's record: ``counts`` (what the driver counted, with
host-clock times), ``trace`` (``trace_reduce.TraceSummary`` of the traced
window, or None), ``tracer`` (its start ``t0`` and stop ``t1``),
``compiles``, ``cfg``, ``peak`` and ``setup_s``.  It returns None where it
finds nothing to read.
"""
from __future__ import annotations

import math


def traced_units(r, key: str, start: str, end: str = "end") -> list:
    """The driver's units of work that lie inside the traced window."""
    t = r.tracer
    if t.t0 is None or t.t1 is None:
        return []
    return [u for u in r.counts.get(key, []) if u[start] >= t.t0 and u[end] <= t.t1]


def window_span(units, start: str) -> float:
    return units[-1]["end"] - units[0][start] if units else 0.0


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (a value that was observed)."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def device_idle(r):
    """Percent of the traced window with no operation on the device."""
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)


def compile_share(r):
    """Percent of the run's window (whole units, host clock) spent in
    backend compilation or persistent-cache loading."""
    units = r.counts.get("units", [])
    if not units:
        return None
    t0, t1 = units[0]["due"], units[-1]["end"]
    return 100.0 * r.compiles.seconds(t0, t1) / (t1 - t0)


def gemm_kernel_s(r):
    """Device seconds in the Pallas GEMM kernel's operations (their names
    are read from the compiled programs by the driver)."""
    if r.trace is None:
        return None
    names = set(r.counts.get("kernel_ops", []))
    s = sum(v for k, v in r.trace.op_name_s.items() if k in names)
    return s if s > 0 else None


def ttft_ms(r, q: float):
    """The ``q`` quantile (nearest rank) over every request of the window of
    the time from its due time (its batch was issued) to its first token,
    in ms.  Only where a request gets one token, so that ``generate``
    returning is the first token."""
    units = r.counts.get("units", [])
    if not units or r.counts["new_tokens"] != 1:
        return None
    ttft = [u["end"] - u["due"] for u in units for _ in range(u["requests"])]
    return 1e3 * nearest_rank(ttft, q)
