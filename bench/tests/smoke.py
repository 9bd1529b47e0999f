"""Tiny copies of the cells for CPU tests: a copy of ``bench/`` in a
temporary directory with smoke-size configurations and cells added as
files, and a ``BENCHMARK.json`` beside it that names them.  Run them with
``CPU`` (the keyword arguments for ``run_cell.run``, ``run_cell.context``
and ``control.readings``)."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

#: the CPU in the chip's place, Pallas kernels run by the interpreter
CPU = {"platform": "cpu", "interpret": True}

SMOKE_CONFIGS = {
    "olmo-smoke": {"program_arch": "olmo-1b", "num_hidden_layers": 2, "hidden_size": 64,
                   "num_attention_heads": 4, "num_key_value_heads": 4,
                   "intermediate_size": 128, "vocab_size": 128},
    "qwen-smoke": {"program_arch": "qwen2-7b", "num_hidden_layers": 2, "hidden_size": 64,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "intermediate_size": 160, "vocab_size": 128},
}


#: limits of the smoke cells, from their own readings on the CPU: the
#: program's served logits lie within 0.03 of the reference's largest
#: (relative), and never pick a token more than 0.004 below its best; the
#: float8 control reads 0.148 or more on the logits.  A served token never
#: exceeds twice its logits' error (limit 0: exact).  A bf16 GEMM output is
#: within 2^-8 (half an ulp) of the exact one, the control reads 0.035 or
#: more.  (The smoke cells keep every request: check_requests exceeds
#: what a 2-second window serves.)  Each cell compares what its full-size
#: cell compares.
DECODE_LIMITS = {"served_token_excess": 0, "logit_rel_error": 0.04}
PREFILL_LIMITS = {"served_logit_gap": 0.05, "logit_rel_error": 0.04}


def smoke_copy(tmp: Path) -> Path:
    """``tmp/bench`` with smoke cells ``olmo-smoke.decode``,
    ``qwen-smoke.prefill`` and ``gemm-smoke.bf16``; returns its path."""
    dst = tmp / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for name, doc in SMOKE_CONFIGS.items():
        base = json.loads((BENCH / "configs" /
                           ("olmo-1b.json" if "olmo" in name else "qwen2-7b-4l.json")).read_text())
        base.update(doc)
        base["reduced"] = [k for k in doc if k != "program_arch"]
        (dst / "configs" / f"{name}.json").write_text(json.dumps(base))
    gemm = json.loads((BENCH / "configs" / "deepbench-gemm.json").read_text())
    gemm["shapes"] = [[64, 128, 256], [35, 20, 64]]
    (dst / "configs" / "gemm-smoke.json").write_text(json.dumps(gemm))
    cells = {
        "olmo-smoke.decode": ("olmo-smoke", "olmo-1b.batch-decode",
                              {"batch": 2, "prompt_len": 8, "new_tokens": 4,
                               "check_requests": 64, "limits": DECODE_LIMITS}),
        "qwen-smoke.prefill": ("qwen-smoke", "qwen2-7b-4l.long-prefill",
                               {"batch": 2, "prompt_len": 16, "new_tokens": 1,
                                "check_requests": 64, "limits": PREFILL_LIMITS}),
        "gemm-smoke.bf16": ("gemm-smoke", "deepbench-gemm.bf16",
                            {"rounds_per_program": 2,
                             "limits": {"gemm_rel_error": 2.0 ** -7}}),
    }
    for name, (config, like, traffic) in cells.items():
        cell = json.loads((BENCH / "workloads" / f"{like}.json").read_text())
        cell["config"] = config
        cell["traffic"].update(traffic)
        (dst / "workloads" / f"{name}.json").write_text(json.dumps(cell))
        entry = next(w for w in bm["workloads"] if w["name"] == like)
        bm["workloads"].append(dict(entry, name=name, config=config))
        for m in bm["end_to_end"] + bm["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    # the CPU stands in for the chip, at the chip's peaks
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (dst / "peaks.json").write_text(json.dumps(peaks))
    return dst
