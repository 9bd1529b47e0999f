#!/usr/bin/env python3
"""Split the longest idle gaps of one traced benchmark run by what the host
was doing in them, from the program's spans (``repro.runtime.spans``) and
JAX's compile events.

    python3 scripts/idle_gaps.py --workload olmo-1b.batch-decode \\
        --seed 1 --seconds 30 --out gaps.json

It runs ``bench/run_cell.py`` with ``--trace 1`` in this process, so it
needs the chip the cell asks for.  While the run reduces its profile, this
script reads the same profile: the first device's busy intervals inside
``bench.window`` and the host events of the program's spans.  The
profile's clock is mapped to ``time.perf_counter``
by matching the spans' profiler annotations to their in-memory records.

For each of the ten longest idle gaps: the innermost program span over its
middle (with its ``step``), and the milliseconds of the gap that JAX's
jaxpr traces, MLIR lowerings and backend compiles (a compile or a load
from the persistent cache) cover, and what no compile event covers.  Also
the host cost of one ``span`` (microseconds, with no profiler session).
It prints one JSON object and writes it to ``--out`` if given.  A cell
whose window runs no program span (the GEMM cell) gets no gaps.
"""
from __future__ import annotations

import argparse
import bisect
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run_cell, trace_reduce  # noqa: E402
from repro.runtime import spans  # noqa: E402

#: ``jax.monitoring`` duration events -> the kind of host work they time
EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
    "/jax/core/compile/backend_compile_duration": "compiles",
}
GAPS = 10


class EventLog:
    """JAX's compile events as (kind, start, end) on ``perf_counter``."""

    def __init__(self):
        self.events = []

    def __call__(self, event, duration, **kw):
        kind = EVENTS.get(event)
        if kind is not None:
            end = time.perf_counter()
            self.events.append((kind, end - duration, end))


def clock_offset(profile, recs) -> tuple[float, float]:
    """(offset, spread) in seconds: profile seconds less ``perf_counter``
    seconds, from the spans' annotations matched to their records; spread
    is the median distance of an annotation from its nearest record."""
    names = {r.name for r in recs}
    ann = [(ev.name, ev.start_ns * 1e-9)
           for plane in profile.planes if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events if ev.name in names]
    if not ann:
        raise ValueError("the profile holds none of the program's spans")
    starts = {}
    for r in recs:
        starts.setdefault(r.name, []).append(r.start)
    for v in starts.values():
        v.sort()

    def nearest(name, t):
        v = starts[name]
        i = bisect.bisect_left(v, t)
        return min(abs(t - v[j]) for j in (i - 1, i) if 0 <= j < len(v))

    name0, t0 = ann[0]
    best = None
    for cand in starts[name0]:
        off = t0 - cand
        res = statistics.median(nearest(n, t - off) for n, t in ann)
        if best is None or res < best[1]:
            best = (off, res)
    return best


def gaps_of(profile, n: int = GAPS) -> list:
    """The ``n`` longest idle (start, end) gaps of the first device inside
    ``bench.window``, in profile seconds."""
    (w0, w1), = [(s, e) for name, s, e in trace_reduce.host_spans(profile)
                 if name == trace_reduce.WINDOW_SPAN][:1]
    devices = trace_reduce.device_ops(profile)
    ops = devices[sorted(devices)[0]]
    busy = trace_reduce.union([(max(s, w0), min(e, w1)) for _, s, e in ops
                               if e > w0 and s < w1])
    gaps, t = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > t:
            gaps.append((t * 1e-9, s * 1e-9))
        t = max(t, e)
    return sorted(gaps, key=lambda g: g[0] - g[1])[:n]


def split_gap(s: float, e: float, recs, events) -> dict:
    """What the host did in the gap [s, e) (``perf_counter`` seconds)."""
    mid = (s + e) / 2
    over = [r for r in recs if r.start <= mid < r.end]
    inner = min(over, key=lambda r: r.seconds) if over else None
    out = {"gap_ms": 1e3 * (e - s),
           "span": inner.name if inner else "no span",
           "step": inner.attrs.get("step") if inner else None}
    allev = []
    for kind in EVENTS.values():
        iv = trace_reduce.union([(a, b) for k, a, b in events if k == kind])
        out[f"{kind}_ms"] = 1e3 * trace_reduce.covered(iv, s, e)
        allev.extend(iv)
    out["no_compile_event_ms"] = 1e3 * (
        (e - s) - trace_reduce.covered(trace_reduce.union(allev), s, e))
    return out


def span_cost_us(n: int = 20000) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        with spans.span("idle_gaps.cost"):
            pass
    return 1e6 * (time.perf_counter() - t0) / n


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    log = EventLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    found = {}
    reduce = trace_reduce.reduce

    def reading(profile, attribute=()):
        recs = [r for r in spans.records() if not r.name.startswith("isam.")]
        if recs:        # the GEMM cell runs no program span in its window
            off, spread = clock_offset(profile, recs)
            found["offset_spread_s"] = spread
            found["gaps"] = [split_gap(s - off, e - off, recs, log.events)
                             for s, e in gaps_of(profile)]
        return reduce(profile, attribute)

    trace_reduce.reduce = reading
    result = run_cell.run(["--workload", args.workload, "--seed",
                           str(args.seed), "--seconds", str(args.seconds),
                           "--trace", "1"])
    found["correct"] = result["correct"]
    found["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
    found["window_s"] = result["device"].get("window_s")
    found["busy_s"] = result["device"].get("busy_s")
    found["span_cost_us"] = span_cost_us()
    text = json.dumps(found, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
