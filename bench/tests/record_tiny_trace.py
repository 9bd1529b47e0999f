#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench.py`` reduces: a jitted
matmul run 8 times inside the benchmark's ``bench.window`` span, the last
4 of them inside a ``bench.decode`` span.  Run on one TPU chip, from the
repository root:

    python bench/tests/record_tiny_trace.py <out_dir>

It writes ``v5e_tiny.xplane.pb`` and ``v5e_tiny.json`` (what the reduction
read from it when it was recorded) into ``out_dir``; copy both into
``bench/tests/data``.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import trace_reduce  # noqa: E402


def main(out: Path) -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda a, b: jnp.tanh(a @ b))
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    f(a, a).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(4):
                f(a, a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.decode"):
                for _ in range(4):
                    f(a, a).block_until_ready()
        time.sleep(0.1)     # let the device's last events reach the trace
        jax.profiler.stop_trace()
        src = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, out / "v5e_tiny.xplane.pb")
    s = trace_reduce.reduce(trace_reduce.load(out / "v5e_tiny.xplane.pb"))
    facts = {"window_s": s.window_s, "busy_s": s.busy_s, "op_pattern": "fusion|convolution",
             "span_busy_s": s.span_busy_s, "ops": s.op_s}
    (out / "v5e_tiny.json").write_text(json.dumps(facts, indent=1))
    print(json.dumps({k: facts[k] for k in ("window_s", "busy_s", "span_busy_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
