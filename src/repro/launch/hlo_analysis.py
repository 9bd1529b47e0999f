"""Post-compile HLO analysis: collective-traffic accounting + roofline terms.

``collective_bytes`` is not part of ``cost_analysis()``; we parse the
optimized HLO text and sum *operand* sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute op, per instructions.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from ..core.dtypes import DTYPE_BYTES as _DTYPE_BYTES
from ..core.sysgraph import GPU_HBM_BW, GPU_NVLINK_BW, GPU_PEAK_FLOPS

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[a-z0-9\[\],{}\s/#_:$extuple()]+?\)?)\s+"
    r"([\w\-]+)\(", re.IGNORECASE)


def shape_bytes(shape_str: str) -> int:
    """Bytes of one 'bf16[8,128]'-style shape (tuples handled upstream)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_kind.values())

    def as_dict(self) -> dict:
        return {"total_bytes": self.total_bytes,
                "total_count": self.total_count,
                "bytes_by_kind": dict(self.bytes_by_kind),
                "count_by_kind": dict(self.count_by_kind)}


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum operand sizes of every collective in optimized HLO text.

    Builds a symbol table of instruction result shapes, then resolves each
    collective's operand names; falls back to the op's own result shape when
    operands cannot be resolved (conservative, still a lower bound).
    """
    shapes: dict[str, str] = {}
    lines = hlo_text.splitlines()
    instr_re = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^)]*\))|(?:[a-z0-9]+"
        r"\[[0-9,]*\](?:\{[^}]*\})?))\s+([\w\-]+)")
    for ln in lines:
        m = instr_re.match(ln)
        if m:
            shapes[m.group(1)] = m.group(2)

    stats = CollectiveStats()
    for ln in lines:
        m = instr_re.match(ln)
        if not m:
            continue
        name, result_shape, op = m.groups()
        kind = next((c for c in COLLECTIVES
                     if op == c or op.startswith(c + "-")), None)
        if kind is None:
            continue
        # operand names inside the parens
        paren = ln[ln.find("("):]
        operand_names = re.findall(r"%?([\w.\-]+)", paren)
        nbytes = 0
        for on in operand_names:
            if on in shapes:
                nbytes += shape_bytes(shapes[on])
        if nbytes == 0:
            nbytes = shape_bytes(result_shape)
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + nbytes
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


# --------------------------------------------------------------------------- #
# Roofline terms (v5e constants; see DESIGN.md §7)
# --------------------------------------------------------------------------- #

PEAK_FLOPS = 197e12         # bf16 / chip
HBM_BW = 819e9              # bytes/s / chip
ICI_BW = 50e9               # bytes/s / link

#: --target name -> (peak FLOP/s, HBM bytes/s, interconnect bytes/s) per
#: chip/device — the modeled machines of ``core.sysgraph``.  The dry-run
#: driver selects a row so the same lowered HLO yields a per-target
#: roofline (the nightly cross-backend sweep).
TARGET_ROOFLINES = {
    "tpu_v5e": (PEAK_FLOPS, HBM_BW, ICI_BW),
    "gpu_sm": (GPU_PEAK_FLOPS, GPU_HBM_BW, GPU_NVLINK_BW),
}


def roofline_terms(flops: float, hbm_bytes: float, collective_bytes: float,
                   chips: int, target: str = "tpu_v5e") -> dict:
    try:
        peak, hbm_bw, link_bw = TARGET_ROOFLINES[target]
    except KeyError:
        raise KeyError(f"no roofline peaks for target {target!r}; known: "
                       f"{sorted(TARGET_ROOFLINES)}") from None
    compute_s = flops / (chips * peak)
    memory_s = hbm_bytes / (chips * hbm_bw)
    collective_s = collective_bytes / (chips * link_bw)
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    terms["dominant"] = dom
    terms["roofline_s"] = bound
    terms["roofline_fraction_compute"] = (
        compute_s / bound if bound > 0 else 0.0)
    return terms
