#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, for a list of
seeds, in one process that holds the chip:

    python bench/control.py --workload <cell> --seeds 1 2 3 ... [--control]

For each seed: the cell's set-up, the shortest window that finishes as many
requests (or rounds) as a run compares, then the compared number read from
the program's output and, with ``--control``, from the plain reference put
in the program's place at the precision below the configuration's (float8).
The program's readings over a dozen seeds or more give the limit's lower
reading, the control's its upper one.  One JSON line per seed.  Not part of
a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import harness, run_cell  # noqa: E402


def readings(cell_name, seeds, control, bench_dir=run_cell.BENCH_DIR, **device):
    """One row per seed; ``device`` goes to ``run_cell.context``."""
    cell = harness.load_cell(cell_name, bench_dir)
    for seed in seeds:
        t = time.perf_counter()
        ctx, _, driver = run_cell.context(cell, seed, 0.0, False, bench_dir, **device)
        state = driver.setup(ctx)
        driver.minimal(ctx, state)
        driver.release(state)
        row = {"cell": cell_name, "seed": seed, "program": driver.reading(ctx, state)}
        if control:
            row["control"] = driver.reading(ctx, state, control=True)
        row["seconds"] = time.perf_counter() - t
        yield row
        del state


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    try:
        for row in readings(args.workload, args.seeds, args.control):
            print(json.dumps(row), flush=True)
    except run_cell.NoDevice as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
