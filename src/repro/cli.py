"""The ``repro`` console script — one entry point for every CLI in the repo.

    repro tune --suite gemm --trials 32        # repro.search.tune
    repro model train --suite gemm,conv ...    # repro.search.model
    repro compile --suite smoke --validate     # repro.compile
    repro graph --validate --cache arts.json   # repro.graph (CompiledGraph)
    repro fabric --shape 5124x700x2048 ...     # repro.fabric.simulate
    repro dryrun --all --mesh both             # repro.launch.dryrun
    repro train / repro serve                  # repro.launch.{train,serve}
    repro servesim --compare --requests 64     # repro.serve (batching sim)
    repro bench --only tuned --json out.json   # benchmarks.run (repo checkout)

Installed via ``[project.scripts]``, so a ``pip install -e .`` is enough —
no ``PYTHONPATH=src`` stanzas; the CI workflows rely on this.  Each
subcommand defers to the module's own ``main``/argparse, so ``repro tune
--help`` shows exactly what ``python -m repro.search.tune --help`` does.
"""
from __future__ import annotations

import sys

#: subcommand -> (module, description).  Modules import lazily: several pull
#: in jax, and the dispatcher must stay instant for --help.
COMMANDS = {
    "tune": ("repro.search.tune", "joint mapping/schedule autotuner"),
    "model": ("repro.search.model", "learned cost model train/eval/export"),
    "compile": ("repro.compile.__main__", "compilation driver CLI"),
    "verify": ("repro.verify.cli", "static analyzer sweep + mutation "
                                   "harness"),
    "graph": ("repro.graph.__main__", "whole-model graph trace/fuse/"
                                      "compile"),
    "fabric": ("repro.fabric.simulate", "multi-chip fabric simulator"),
    "dryrun": ("repro.launch.dryrun", "dry-run roofline matrix"),
    "train": ("repro.launch.train", "training launch"),
    "serve": ("repro.launch.serve", "serving launch"),
    "servesim": ("repro.serve.__main__", "online continuous-batching "
                                         "serving simulator"),
    "bench": ("benchmarks.run", "benchmark harness (needs the repo "
                                "checkout on sys.path / as cwd)"),
}


def _usage(out=sys.stderr) -> None:
    print("usage: repro <command> [args...]\n\ncommands:", file=out)
    for name, (_, desc) in COMMANDS.items():
        print(f"  {name:<9} {desc}", file=out)
    print("\n'repro <command> --help' shows the command's own options.",
          file=out)


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        _usage(sys.stdout if argv else sys.stderr)
        return 0 if argv else 2
    cmd, rest = argv[0], argv[1:]
    if cmd not in COMMANDS:
        print(f"repro: unknown command {cmd!r}", file=sys.stderr)
        _usage()
        return 2
    module_name = COMMANDS[cmd][0]
    import importlib
    if cmd == "bench":
        # Console scripts don't put the cwd on sys.path, and the benchmarks
        # package ships with the repo checkout, not the wheel.
        import os
        if os.path.isfile(os.path.join(os.getcwd(), "benchmarks",
                                       "run.py")) \
                and os.getcwd() not in sys.path:
            sys.path.insert(0, os.getcwd())
    try:
        module = importlib.import_module(module_name)
    except ImportError as e:
        print(f"repro {cmd}: cannot import {module_name} ({e})",
              file=sys.stderr)
        if cmd == "bench":
            print("the benchmarks package lives in the repo checkout, not "
                  "the installed wheel — run from the repo root",
                  file=sys.stderr)
        return 2
    run = getattr(module, "main", None)
    if run is None:                     # pragma: no cover - all have main()
        print(f"repro {cmd}: {module_name} has no main()", file=sys.stderr)
        return 2
    # Modules whose main() calls sys.exit / parses sys.argv directly get
    # the argv slice spliced in; ours all accept an argv parameter or use
    # argparse's default (sys.argv), so rewrite sys.argv for uniformity.
    sys.argv = [f"repro {cmd}"] + rest
    try:
        ret = run()
    except SystemExit as e:
        if isinstance(e.code, str):      # sys.exit("message") convention
            print(e.code, file=sys.stderr)
            return 1
        return int(e.code or 0)
    # A main() may return a result (serve: tokens, train: losses) rather
    # than an exit status; only an int is a status.
    return ret if isinstance(ret, int) else 0


if __name__ == "__main__":
    raise SystemExit(main())
