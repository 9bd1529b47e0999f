"""Autotuning walkthrough: search the mapping/schedule space, persist the
winner, and hand it to the Pallas GEMM (paper Section 4).

    PYTHONPATH=src python examples/autotune_gemm.py

1. Build a GEMM haystack and select the MXU matmul instruction.
2. Search the ParamApproach config space (tile shapes, reduction streaming,
   VMEM budget, unroll order, device/copy policies) against the static
   scheduler's cost model — the greedy-equivalent baseline is trial 0, so
   the result can only match or beat the paper's heuristics.
3. Validate: the winning schedule replays bit-exact against the ISAMIR
   oracle through the executor.
4. Persist the winner in the tuning cache and read it back.
5. Pass the recorded tile to the Pallas GEMM as ``block=`` (the kernels
   never read the cache themselves).

The same flow over the paper's full evaluation set is the CLI:

    PYTHONPATH=src python -m repro.search.tune --suite gemm --trials 32
"""
import os
import tempfile

import numpy as np

from repro.core import instructions as I
from repro.core import kernels_ir as K
from repro.core.isel import select_instructions
from repro.core.sysgraph import tpu_v5e
from repro.search.cache import TuningCache, TuningRecord, lookup_gemm
from repro.search.evaluate import (CostModelEvaluator, gemm_tile_for,
                                   validate_selection)
from repro.search.space import ParamApproach, SearchSpace, tuning_key
from repro.search.strategies import hill_climb

M, N, KDIM = 1024, 128, 1024

# 1. map + select ------------------------------------------------------------
prog = K.matmul(M, N, KDIM)
sel = select_instructions(prog, [I.mxu_matmul()], allow_transforms=False)
graph = tpu_v5e(1)

# 2. search ------------------------------------------------------------------
space = SearchSpace.for_graph(graph)
evaluate = CostModelEvaluator(sel, graph)
outcome = hill_climb(space, evaluate, trials=24, seed=0)
print(f"== search: {outcome.evaluations} trials ==")
print(f"greedy baseline : {outcome.baseline_cost * 1e6:8.2f} us (modeled)")
print(f"tuned           : {outcome.best_cost * 1e6:8.2f} us "
      f"({outcome.speedup:.2f}x)")
changed = {k: v for k, v in outcome.best_config.items()
           if v != space.baseline()[k]}
print(f"winning moves   : {changed or 'none (greedy is optimal here)'}")

# 3. oracle validation --------------------------------------------------------
report = validate_selection(prog, sel, graph,
                            ParamApproach(outcome.best_config))
assert report.exact, report
print("tuned schedule replays bit-exact against the ISAMIR oracle")

# 4. persist + read back -------------------------------------------------------
cache_path = os.path.join(tempfile.mkdtemp(prefix="repro_tune_"),
                          "tuning.json")
cache = TuningCache(cache_path)
cache.store(TuningRecord(
    key=tuning_key(prog, graph, "cost"), config=outcome.best_config,
    cost=outcome.best_cost, baseline_cost=outcome.baseline_cost,
    strategy="hillclimb", trials=outcome.evaluations,
    tile=gemm_tile_for(outcome.best_config, graph, M, N, KDIM)))
rec = lookup_gemm(M, N, KDIM, cache=cache)
print(f"cache {cache_path}: tile={rec.tile} "
      f"speedup={rec.speedup:.2f}x")

# 5. tuned Pallas kernel --------------------------------------------------------
import jax.numpy as jnp

from repro.kernels.gemm import gemm
from repro.kernels.ref import gemm_ref

block = rec.tile
rng = np.random.default_rng(0)
a = jnp.asarray(rng.uniform(-1, 1, (M, KDIM)), jnp.float32)
b = jnp.asarray(rng.uniform(-1, 1, (KDIM, N)), jnp.float32)
out = gemm(a, b, block=block, interpret=True)
np.testing.assert_allclose(np.asarray(out), np.asarray(gemm_ref(a, b)),
                           rtol=1e-4, atol=1e-4)
print(f"Pallas GEMM with tuned BlockSpec {block}: OK")
