"""The compiler spine is one-way: ``kernels -> compile -> core``.

The modeled add-ons (``search``, ``fabric``, ``graph``, ``serve``) and the
model stack (``launch``, ``models``) build on the spine; the spine never
imports them, so a planned tile depends on the GEMM shape alone — no tuning
cache, learned-model store or environment variable can change it.  Pinned
here: the import edges (statically and at run time), the tiles
``plan_gemm`` plans for the 8 DeepBench GEMM shapes, and the fingerprints
and artifact key every persistent cache is keyed on.
"""
from __future__ import annotations

import ast
import os
import subprocess
import sys
import textwrap

import pytest

from repro.compile import compile_gemm
from repro.compile.cache import (jax_version, program_fingerprint,
                                 sysgraph_fingerprint)
from repro.core import kernels_ir as K
from repro.core.sysgraph import tpu_v5e
from repro.kernels.ops import plan_gemm

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

#: packages above the spine
ABOVE = ("repro.search", "repro.fabric", "repro.graph", "repro.serve",
         "repro.launch", "repro.models")

#: (module, enclosing function) -> the one module above the spine it may
#: import: rebuilding a cache-hydrated fabric artifact's shard-0 schedule.
ALLOWED = {("repro.compile.driver", "recompile_schedule"):
           "repro.fabric.partition"}


def _under(name: str, package: str) -> bool:
    return name == package or name.startswith(package + ".")

#: the 8 DeepBench GEMM shapes and the tile the compiler plans for each
DEEPBENCH_TILES = {
    (1024, 128, 1024): (128, 128, 1024),
    (2048, 64, 2048): (128, 64, 2048),
    (1760, 128, 1760): (128, 128, 1760),
    (2560, 64, 2560): (128, 64, 2560),
    (5124, 700, 2048): (128, 700, 2048),
    (3072, 128, 1024): (128, 128, 1024),
    (35, 700, 2048): (35, 700, 2048),
    (7680, 1, 2560): (128, 1, 2560),
}


def _imports(path: str, module: str, is_pkg: bool):
    """(enclosing function or None, absolute imported module) for every
    import in ``path``, lazy ones included."""
    package = module if is_pkg else module.rpartition(".")[0]
    found = []

    def visit(node, func):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit(child, child.name)
                continue
            if isinstance(child, ast.Import):
                found.extend((func, a.name) for a in child.names)
            elif isinstance(child, ast.ImportFrom):
                if child.level:
                    base = package.split(".")
                    base = base[:len(base) - (child.level - 1)]
                    base = ".".join(base + ([child.module]
                                            if child.module else []))
                else:
                    base = child.module
                found.append((func, base))
                found.extend((func, f"{base}.{a.name}")
                             for a in child.names)
            visit(child, func)

    with open(path) as f:
        visit(ast.parse(f.read(), path), None)
    return found


@pytest.mark.parametrize("layer", ["core", "compile", "kernels"])
def test_spine_imports_nothing_above_it(layer):
    root = os.path.join(SRC, "repro", layer)
    bad = []
    for dirpath, _, files in os.walk(root):
        for name in sorted(files):
            if not name.endswith(".py") or name == "__main__.py":
                continue
            rel = os.path.relpath(os.path.join(dirpath, name), SRC)
            module = rel[:-3].replace(os.sep, ".")
            is_pkg = name == "__init__.py"
            if is_pkg:
                module = module.rpartition(".")[0]
            for func, imported in _imports(os.path.join(dirpath, name),
                                           module, is_pkg):
                allowed = ALLOWED.get((module, func))
                if (any(_under(imported, p) for p in ABOVE)
                        and not (allowed and _under(imported, allowed))):
                    bad.append(f"{module}:{func or '<module>'} -> {imported}")
    assert not bad, bad


def test_planning_loads_no_addon():
    code = textwrap.dedent(f"""
        import sys
        from repro.compile import compile_gru
        from repro.kernels.ops import plan_gemm
        for m, n, k in {sorted(DEEPBENCH_TILES)!r}:
            plan_gemm(m, n, k)
        compile_gru(32, 64, use_cache=False)
        print(sorted(m for m in sys.modules if m.startswith(
            ("repro.search", "repro.fabric", "repro.graph", "repro.serve"))))
    """)
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "[]"


@pytest.mark.parametrize("shape", sorted(DEEPBENCH_TILES))
def test_plan_gemm_tile_is_the_plan(shape, tmp_path, monkeypatch):
    from repro.search.cache import (TuningCache, TuningRecord,
                                    gemm_tuning_key, set_default_cache)
    tile = DEEPBENCH_TILES[shape]
    assert plan_gemm(*shape, use_cache=False)[0] == tile
    # A tuning cache recording another tile changes nothing.
    path = str(tmp_path / "tuning.json")
    TuningCache(path).store(TuningRecord(
        key=gemm_tuning_key(*shape), config={}, cost=1e-9, baseline_cost=1.0,
        tile=(8, 128, 128)))
    monkeypatch.setenv("REPRO_TUNING_CACHE", path)
    monkeypatch.setenv("REPRO_COMPILE_CACHE", str(tmp_path / "compiled.json"))
    set_default_cache(None)
    try:
        assert plan_gemm(*shape)[0] == tile
    finally:
        set_default_cache(None)


@pytest.mark.parametrize("what,got,want", [
    ("fingerprints",
     lambda: (program_fingerprint(K.matmul(1024, 128, 1024)),
              *(sysgraph_fingerprint(tpu_v5e(n)) for n in (1, 2, 4))),
     lambda: ("592a6833d41ca1b5", "0d97d941208b9ab7", "5eed5e5d63a745eb",
              "373c1d5ef558b3ae")),
    ("artifact_key",
     lambda: compile_gemm(1024, 128, 1024, use_cache=False).key,
     lambda: ("matmul_1024x128x1024@592a6833d41ca1b5"
              "|tpu_v5e_x1@0d97d941208b9ab7|greedy|cost|isa=0848669339e0"
              f"|xf=0|jax={jax_version()}")),
])
def test_cache_keys_pinned(what, got, want):
    assert got() == want(), what
