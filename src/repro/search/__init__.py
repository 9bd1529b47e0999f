"""repro.search — joint mapping/schedule autotuning (paper Section 4).

The paper frames the compiler's combinatorial choices as a *flexible
framework that allows heuristics, cost models, and potentially machine
learning*.  This package is that framework's search driver:

  * ``space``      — ``ParamApproach``: every Approach decision point driven
                     by an explicit, enumerable config vector; the tuning
                     key over ``repro.compile``'s fingerprints.
  * ``strategies`` — seeded, deterministic search strategies over the space
                     (random sampling, greedy hill-climb, evolutionary).
  * ``evaluate``   — evaluation backends: fast ``scheduler.cost_model()``
                     dry-runs and optional measured Pallas wall-clock, plus
                     executor-vs-oracle validation of winning schedules.
  * ``cache``      — persistent JSON tuning cache keyed by (program
                     fingerprint, sysgraph, backend, jax version), read back
                     by the tuner, ``repro verify --tuned`` and the modeled
                     benchmarks; ``repro.kernels`` never reads it.
  * ``features``   — engineered feature vectors over (config, program,
                     graph) triples, the learned model's inputs.
  * ``batch``      — vectorized population guard, schedule keys and the
                     incremental ``DeltaScheduler``.
  * ``model``      — the **learned** cost model: deterministic numpy ridge
                     regression over engineered feature vectors, trained
                     from cache records + fresh cost-model labels, stored as
                     JSON artifacts keyed per (program family, sysgraph,
                     backend, jax version); drives ``surrogate`` search.
  * ``tune``       — the ``python -m repro.search.tune`` CLI.
"""
from .cache import TuningCache, TuningRecord, default_cache_path, get_default_cache
from .space import ParamApproach, SearchSpace, tuning_key
from .strategies import STRATEGIES, SearchOutcome, Trial

_MODEL_EXPORTS = ("CostModel", "ModelStore", "default_store_path",
                  "model_key")


def __getattr__(name):
    # Lazy: ``python -m repro.search.model`` must not find the submodule
    # pre-imported (runpy warns), and the cache/space fast paths shouldn't
    # pay for numpy-heavy model code they never use.
    if name in _MODEL_EXPORTS:
        from . import model
        return getattr(model, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "ParamApproach", "SearchSpace", "tuning_key",
    "STRATEGIES", "SearchOutcome", "Trial",
    "TuningCache", "TuningRecord", "default_cache_path", "get_default_cache",
    "CostModel", "ModelStore", "default_store_path", "model_key",
]
