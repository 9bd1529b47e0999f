"""Persistent ``CompiledKernel`` artifact cache and the fingerprints it keys on.

The key is (program fingerprint, sysgraph fingerprint, *approach*
fingerprint, backend, jax version), so an artifact is reused only when the
whole compile is reproducible — a different machine description, a
different config vector or a toolchain bump all miss.  The fingerprints
hash ``Program.signature()`` and the system graph's node/edge structure
rather than names alone, so keys survive process restarts; the
``repro.search`` tuning cache keys on the same ones.  One JSON file,
atomic writes under an advisory file lock, warn-once on a corrupt file.

The process-wide default cache is *opt-in* (``set_default_artifact_cache``):
library entry points like ``plan_gemm`` stay purely in-memory-memoized
unless the CLI or a test activates a cache.
"""
from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import os
import tempfile
import warnings

from ..core.ir import Program
from ..core.sysgraph import SystemGraph
from .artifact import ARTIFACT_SCHEMA, CompiledKernel

try:                                    # POSIX advisory locks
    import fcntl
except ImportError:                     # pragma: no cover - non-POSIX
    fcntl = None

#: Override the default artifact-cache location (e.g. in CI).
CACHE_ENV_VAR = "REPRO_COMPILE_CACHE"

#: The error types a persistent-cache lookup can legitimately raise — what
#: the artifact cache and the ``repro.search`` stores catch instead of a
#: bare ``Exception``.
CACHE_ERRORS = (OSError, ValueError, KeyError, TypeError)

_warned_corrupt: set[str] = set()


def warn_corrupt_cache(path: str, err: Exception) -> None:
    """Warn exactly once per path about an unreadable cache file (every
    persistent cache degrades a corrupt file to an empty cache, but never
    silently)."""
    if path in _warned_corrupt:
        return
    _warned_corrupt.add(path)
    warnings.warn(f"ignoring corrupt cache file {path}: {err}", stacklevel=3)


@contextlib.contextmanager
def file_lock(path: str):
    """Advisory inter-process lock on ``path + '.lock'``.

    Serializes the merge-on-save read-modify-write of the persistent caches
    so parallel tuner workers (``tune --workers N``) cannot interleave
    between a save's re-read and its atomic replace — without the lock a
    racing pair can each merge against the *pre*-race file and the second
    ``os.replace`` silently drops the first writer's keys.  Locking is
    best-effort: on platforms without ``fcntl`` the context is a no-op and
    saves fall back to the documented last-writer-wins-per-key race."""
    if fcntl is None:                   # pragma: no cover - non-POSIX
        yield
        return
    lock_path = os.path.abspath(path) + ".lock"
    os.makedirs(os.path.dirname(lock_path), exist_ok=True)
    with open(lock_path, "w") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)


# --------------------------------------------------------------------------- #
# Fingerprints
# --------------------------------------------------------------------------- #


def _short_hash(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@functools.lru_cache(maxsize=512)
def program_fingerprint(prog: Program) -> str:
    """Stable structural hash of a haystack program (axes, buffers, access
    matrices) — survives renaming-free rebuilds across processes.  Cached
    (Program is frozen/hashable): artifact keying sits on the evaluator hot
    path and re-fingerprints the same program every trial."""
    return _short_hash(prog.signature())


def sysgraph_fingerprint(graph: SystemGraph) -> str:
    """Structural hash of a system graph: target family, memory
    capacities/levels/roles, compute capabilities, and movement edges.
    Two targets that differ in any of these can never share an artifact,
    tuning record or learned model (the cross-backend isolation the
    portability tests pin down)."""
    parts = [graph.name, f"F{getattr(graph, 'family', 'generic')}"]
    for m in sorted(graph.memories.values(), key=lambda m: m.name):
        parts.append(f"M{m.name}:{m.capacity}:{m.level}:{m.role}")
    for c in sorted(graph.computes.values(), key=lambda c: c.name):
        parts.append(f"C{c.name}:{c.memory}:{sorted(c.instructions)}:"
                     f"{c.flops_per_sec}:{c.matmul_tile}:{c.vector_lanes}:"
                     f"{c.clock_hz}")
    for e in sorted(graph.edges, key=lambda e: (e.src, e.dst)):
        parts.append(f"E{e.src}>{e.dst}:{e.bandwidth}:{e.latency}")
    return _short_hash(";".join(parts))


@functools.lru_cache(maxsize=1)
def jax_version() -> str:
    """jax version without importing jax (keeps core/compile numpy-only).
    Cached: the metadata scan costs milliseconds and the result is a
    process-constant, while ``artifact_key``/``tuning_key`` sit on the
    evaluator hot path."""
    try:
        from importlib.metadata import version
        return version("jax")
    except Exception:  # pragma: no cover - metadata unavailable
        return "unknown"


# --------------------------------------------------------------------------- #
# Artifact keys
# --------------------------------------------------------------------------- #


def default_artifact_cache_path() -> str:
    env = os.environ.get(CACHE_ENV_VAR)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "compiled.json")


def approach_fingerprint(approach) -> str:
    """Stable identity of an Approach for artifact keying.

    ``ParamApproach``-style approaches expose their config vector; the
    stateless heuristic approaches reduce to their class name.  Approaches
    with hidden state (wrappers, RNG-driven) get a non-reusable fingerprint
    so they are never served a cached artifact."""
    cfg = getattr(approach, "config", None)
    if isinstance(cfg, dict):
        return "cfg:" + json.dumps(
            {k: cfg[k] for k in sorted(cfg)}, sort_keys=True)
    name = type(approach).__name__ if approach is not None else "GreedyApproach"
    if name in ("GreedyApproach", "Approach"):
        return "greedy"
    if name == "CostModelApproach":
        return f"costmodel:{getattr(approach, 'samples', 0)}" \
               f":{getattr(approach, 'seed', 0)}"
    return f"opaque:{name}:{id(approach)}"


def cacheable_approach(approach) -> bool:
    return not approach_fingerprint(approach).startswith("opaque:")


def isa_fingerprint(isa) -> str:
    """Structural hash of the needle set in play — two compiles of the same
    program under different ISAs must never share an artifact."""
    if not isa:
        return "-"
    parts = sorted(f"{n.name}@{program_fingerprint(n)}" for n in isa)
    return hashlib.sha256(";".join(parts).encode()).hexdigest()[:12]


def artifact_key_from_parts(prog_name: str, prog_fp: str, graph_name: str,
                            graph_fp: str, approach_fp: str, backend: str,
                            isa_fp: str = "-",
                            allow_transforms: bool = True) -> str:
    return (f"{prog_name}@{prog_fp}|{graph_name}@{graph_fp}"
            f"|{approach_fp}|{backend}|isa={isa_fp}"
            f"|xf={int(bool(allow_transforms))}|jax={jax_version()}")


def artifact_key(prog, graph: SystemGraph | str, approach,
                 backend: str = "cost", isa=None,
                 allow_transforms: bool = True) -> str:
    """(program fp, sysgraph fp, approach fp, backend, isa fp, transform
    policy, jax version)."""
    if isinstance(graph, SystemGraph):
        gname, gfp = graph.name, sysgraph_fingerprint(graph)
    else:
        gname, _, gfp = graph.partition("@")
    return artifact_key_from_parts(prog.name,
                                   program_fingerprint(prog),
                                   gname, gfp,
                                   approach_fingerprint(approach), backend,
                                   isa_fingerprint(isa), allow_transforms)


class ArtifactCache:
    """Dict of ``CompiledKernel`` dicts with JSON persistence."""

    def __init__(self, path: str | None = None):
        self.path = path or default_artifact_cache_path()
        self._entries: dict[str, dict] | None = None

    # -- persistence ---------------------------------------------------------
    def load(self) -> dict[str, dict]:
        if self._entries is None:
            entries: dict[str, dict] = {}
            raw = None
            try:
                with open(self.path) as f:
                    raw = json.load(f)
            except OSError:
                pass                          # missing file = empty cache
            except ValueError as e:           # json.JSONDecodeError
                warn_corrupt_cache(self.path, e)
            if isinstance(raw, dict):
                for d in raw.get("artifacts", []):
                    if isinstance(d, dict) and "key" in d:
                        entries[d["key"]] = d
            self._entries = entries
        return self._entries

    def save(self) -> None:
        # Merge-on-save under the advisory file lock: concurrent savers
        # serialize, so parallel tuner workers cannot drop each other's
        # artifacts.
        with file_lock(self.path):
            self._save_locked()

    def _save_locked(self) -> None:
        ours = dict(self.load())
        entries = ArtifactCache(self.path).load()
        entries.update(ours)
        self._entries = entries
        payload = {"schema": ARTIFACT_SCHEMA,
                   "artifacts": list(entries.values())}
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- access --------------------------------------------------------------
    def lookup(self, key: str) -> CompiledKernel | None:
        d = self.load().get(key)
        if d is None:
            return None
        from ..verify import verify_artifact_dict
        diags = verify_artifact_dict(d)
        if diags:
            warn_corrupt_cache(
                self.path,
                ValueError(f"artifact {key!r} failed payload verification: "
                           + "; ".join(str(x) for x in diags[:3])))
            return None
        try:
            return CompiledKernel.from_dict(d)
        except CACHE_ERRORS as e:
            warn_corrupt_cache(self.path, e)
            return None

    def store(self, artifact: CompiledKernel, save: bool = True) -> None:
        self.load()[artifact.key] = artifact.to_dict()
        if save:
            self.save()

    def keys(self):
        return self.load().keys()

    def __len__(self) -> int:
        return len(self.load())

    def __contains__(self, key: str) -> bool:
        return key in self.load()


# --------------------------------------------------------------------------- #
# Process-wide default cache (opt-in)
# --------------------------------------------------------------------------- #

_default_cache: ArtifactCache | None = None


def get_default_artifact_cache() -> ArtifactCache | None:
    """The active artifact cache, or None when none has been activated."""
    return _default_cache


def set_default_artifact_cache(cache: ArtifactCache | None) -> None:
    """Activate (or deactivate) the process-wide artifact cache — used by
    the CLI and tests."""
    global _default_cache
    _default_cache = cache
