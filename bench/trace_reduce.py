"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
one event per operation that ran, named by its HLO instruction text
(``%gemm.1 = f32[5248,700]{...} custom-call(...)``); a control-flow op such
as a scan's ``while`` spans the ops of its body.  The benchmark's own host
spans are the host events whose names start with ``bench.``;
``bench.window`` bounds the traced window, and every device interval is
clipped to it.  Host and device events share one clock in the trace.

- busy: the union of the operation intervals, per device, averaged over
  the devices;
- op self time (what nested ops do not cover) by label ``<name> <type>``,
  summed over devices, and by bare instruction name;
- collective time that no other operation on that device overlaps;
- device busy time attributed to the host span (of the kinds asked for)
  that most recently started before each operation;
- the longest idle gaps, labelled by the innermost benchmark span over
  their middle.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
GAPS_KEPT = 10
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|collectivepermute|alltoall",
    re.IGNORECASE)


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # per device, averaged
    n_devices: int
    op_s: dict = field(default_factory=dict)          # label -> self seconds (all devices)
    op_name_s: dict = field(default_factory=dict)     # instruction name -> self seconds
    collective_exposed_s: float = 0.0                 # per device, averaged
    span_busy_s: dict = field(default_factory=dict)   # span name -> device seconds
    idle_gaps: list = field(default_factory=list)     # [(label, seconds)], longest first

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:n]]}


HLO_TEXT = re.compile(r"^%?([^\s=]+) = (\S+)")


def op_label(text: str) -> tuple[str, str]:
    """(instruction name, ``name type``) of an op event's HLO text."""
    m = HLO_TEXT.match(text)
    if not m:
        return text, text
    kind = re.sub(r"\{[^}]*\}", "", m.group(2))[:48]
    return m.group(1), f"{m.group(1)} {kind}"


def self_times(ops):
    """Self time of each (name, start, end) op: its duration less that of
    the ops nested directly inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e - s for _, s, e in ops]
    stack = []
    for i in order:
        _, s, e = ops[i]
        while stack and not (ops[stack[-1]][1] <= s and e <= ops[stack[-1]][2]):
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals, s, e) -> float:
    """Length of [s, e) covered by merged ``intervals``."""
    tot = 0.0
    for a, b in intervals:
        if b <= s:
            continue
        if a >= e:
            break
        tot += min(b, e) - max(a, s)
    return tot


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def host_spans(profile) -> list:
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return sorted(spans, key=lambda s: s[1])


def device_ops(profile) -> dict:
    """{plane name: [(name, start_ns, end_ns)]} from each device's op line."""
    out = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events)
        out[plane.name] = ops
    return out


def reduce(profile, attribute=("bench.prefill", "bench.decode")) -> TraceSummary:
    """Reduce a loaded profile (``load``) or anything with ``planes``."""
    spans = host_spans(profile)
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    w0, w1 = windows[0][1], windows[0][2]
    devices = device_ops(profile)
    if not devices:
        raise ValueError("the trace has no device plane with XLA ops")

    op_ns: dict = {}
    name_ns: dict = {}
    busy_ns = exposed_ns = 0.0
    all_busy = []
    attr = [s for s in spans if s[0] in attribute]
    attr_starts = [s[1] for s in attr]
    span_busy: dict = {}
    for ops in (devices[d] for d in sorted(devices)):
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        for (n, _, _), own in zip(clipped, self_times(clipped)):
            name, label = op_label(n)
            op_ns[label] = op_ns.get(label, 0.0) + own
            name_ns[name] = name_ns.get(name, 0.0) + own
        busy = union([(s, e) for _, s, e in clipped])
        busy_ns += sum(e - s for s, e in busy)
        all_busy.append(busy)
        compute = union([(s, e) for n, s, e in clipped if not COLLECTIVE.search(n)])
        for n, s, e in clipped:
            if COLLECTIVE.search(n):
                exposed_ns += (e - s) - covered(compute, s, e)
        if attr:
            # busy time, not summed op time: nested ops count once
            for s, e in busy:
                i = bisect.bisect_right(attr_starts, s) - 1
                if i >= 0:
                    name = attr[i][0]
                    span_busy[name] = span_busy.get(name, 0.0) + (e - s)

    nd = len(devices)
    # idle gaps on the first device's timeline (the only one in one-chip cells)
    gaps, t = [], w0
    for s, e in all_busy[0] + [(w1, w1)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:GAPS_KEPT]:
        mid = (s + e) / 2
        over = [sp for sp in inner if sp[1] <= mid < sp[2]]
        label = min(over, key=lambda sp: sp[2] - sp[1])[0] if over else "no span"
        labelled.append((label, (e - s) * 1e-9))
    labelled.sort(key=lambda g: -g[1])

    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_ns / nd * 1e-9,
        n_devices=nd,
        op_s={k: v * 1e-9 for k, v in op_ns.items()},
        op_name_s={k: v * 1e-9 for k, v in name_ns.items()},
        collective_exposed_s=exposed_ns / nd * 1e-9,
        span_busy_s={k: v / nd * 1e-9 for k, v in span_busy.items()},
        idle_gaps=labelled,
    )
