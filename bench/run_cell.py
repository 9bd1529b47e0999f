#!/usr/bin/env python3
"""Run one benchmark cell on the chip(s) and print its result line.

    python bench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run.  It finds the cell (``bench/workloads/<cell>.json``),
its configuration and its driver by name, refuses to run on anything but
as many TPU chips as the cell asks for, turns on the program's compile
cache, lets the driver make weights and inputs from ``--seed`` and warm up
(that is ``setup_s``), drives the cell's traffic for ``--seconds``, reads
the peak device memory, frees the program's state, and compares what the
timed path produced with the plain reference.

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` a few seconds of the window are traced and it holds the
per-layer metrics.  Each metric is read by ``bench/metrics/<metric>.py``.
The last lines of standard error give each compared number beside its
limit; the last line of standard output is the JSON result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

#: seconds of the window that a ``--trace 1`` run traces (whole units of
#: work: it starts and stops between them)
TRACE_SECONDS = 3.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class NoDevice(RuntimeError):
    """The cell's chips are not there."""


class CompileLog:
    """Backend compilations or loads from the persistent cache, as (end
    time on ``time.perf_counter``, seconds), and the times of persistent
    cache misses (real compilations), from ``jax.monitoring``."""

    def __init__(self):
        self.events = []
        self.misses = []

    def __call__(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), duration))

    def on_event(self, event, **kw):
        if event == CACHE_MISS:
            self.misses.append(time.perf_counter())

    def seconds(self, t0: float, t1: float) -> float:
        return sum(d for t, d in self.events if t0 <= t <= t1)

    def counts(self, t0: float, t1: float) -> tuple[int, int]:
        """(programs compiled or loaded, of them compiled) in [t0, t1]."""
        return (sum(t0 <= t <= t1 for t, _ in self.events),
                sum(t0 <= t <= t1 for t in self.misses))


class Tracer:
    """Traces about ``seconds`` of the window, starting and stopping at
    the unit boundaries the driver marks (``boundary``)."""

    def __init__(self, enabled: bool, seconds: float = TRACE_SECONDS):
        self.enabled, self.seconds = enabled, seconds
        self.dir = None
        self.t0 = self.t1 = None
        self._window = None

    def boundary(self):
        if not self.enabled or self.t1 is not None:
            return
        import jax
        now = time.perf_counter()
        if self.t0 is None:
            self.dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir.name, profiler_options=opts)
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()
            self.t0 = time.perf_counter()
        elif now - self.t0 >= self.seconds:
            self.stop()

    def stop(self):
        if self.t0 is None or self.t1 is not None:
            return
        import jax
        self.t1 = time.perf_counter()
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self, attribute):
        from bench import trace_reduce
        if self.t0 is None:
            return None
        self.stop()
        files = sorted(Path(self.dir.name).rglob("*.xplane.pb"))
        if not files:
            return None
        try:
            return trace_reduce.reduce(trace_reduce.load(files[-1]), attribute)
        finally:
            self.dir.cleanup()


def seed_key(seed: int):
    """A PRNG key from any whole number (64 bits of it)."""
    import jax
    seed %= 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def device_info(chips: int, platform: str) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    if d.platform != platform:
        raise NoDevice(f"no {platform}: JAX's first device is {d.platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chip(s), JAX sees {len(devs)}")
    return info


def peak_memory(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def cell_metrics(bm: dict, cell: str, trace: bool) -> list:
    """The metrics this cell reports: its end-to-end ones, or with
    ``trace`` its per-layer ones."""
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in moved)]


def context(cell: dict, seed: int, seconds: float, trace: bool = False,
            bench_dir: Path = BENCH_DIR, platform: str = "tpu",
            interpret: bool = False):
    """Check the device, turn the compile cache on, and build what a
    driver is handed: ``(ctx, device, driver)``.  ``platform`` and
    ``interpret`` (Pallas kernels run by the interpreter) are for tests of
    the harness on the CPU."""
    import jax
    device = device_info(cell["chips"], platform)
    peak = harness.peaks(device["kind"], bench_dir)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, is kept: later runs load it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.on_event)

    driver = harness.load_module("drivers", cell["driver"], bench_dir)
    doc = cell["config_file"]
    ctx = SimpleNamespace(
        cell=cell, traffic=cell["traffic"], seed=seed, key=seed_key(seed),
        seconds=seconds, peak=peak, tracer=Tracer(trace), compiles=compiles,
        interpret=interpret,
        reference=harness.load_module("reference", doc["reference"], bench_dir),
        model_cfg=harness.model_config(doc) if doc.get("program_arch") else None)
    return ctx, device, driver


def run(argv=None, *, bench_dir: Path = BENCH_DIR, t0: float = T0,
        platform: str = "tpu", interpret: bool = False) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bm = harness.benchmark(bench_dir)
    cell = harness.load_cell(args.workload, bench_dir)
    metrics = cell_metrics(bm, args.workload, bool(args.trace))

    ctx, device, driver = context(cell, args.seed, args.seconds, bool(args.trace),
                                  bench_dir, platform, interpret)
    peak, compiles = ctx.peak, ctx.compiles
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t0
    counts = driver.window(ctx, state)
    ctx.tracer.stop()
    t_window = time.perf_counter()
    device["memory_peak_bytes"] = peak_memory(cell["chips"])
    summary = ctx.tracer.summary(driver.ATTRIBUTE)
    driver.release(state)
    t_check = time.perf_counter()
    checks = driver.check(ctx, state, counts)
    loaded, compiled = compiles.counts(t0 + setup_s, t_window)
    print(f"[bench] setup {setup_s:.3f} s, window and trace {t_window - t0 - setup_s:.3f} s,"
          f" trace reduction {t_check - t_window:.3f} s,"
          f" check {time.perf_counter() - t_check:.3f} s; in the window {loaded} programs"
          f" compiled or loaded from the cache, {compiled} of them compiled",
          file=sys.stderr, flush=True)

    record = SimpleNamespace(cell=cell, cfg=ctx.model_cfg, counts=counts,
                             setup_s=setup_s, peak=peak, trace=summary,
                             tracer=ctx.tracer, compiles=compiles)
    values = {}
    for m in metrics:
        v = harness.load_module("metrics", m["name"], bench_dir).read(record)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks)
    result = {"correct": correct, "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": values, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except NoDevice as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
