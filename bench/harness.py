"""What the benchmark finds by name: cells (``workloads/<cell>.json``),
configurations (``configs/<config>.json``), drivers (``drivers/<driver>.py``),
metric readers (``metrics/<metric>.py``) and the peak table
(``peaks.json``).  Adding a cell, a configuration or a metric is adding
files; nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent

#: the published configuration's key -> the program's ``ModelConfig`` field
CONFIG_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
}
#: the configuration file's ``layer_norm`` -> the program's ``norm``
NORMS = {"rmsnorm": "rmsnorm", "nonparametric_layernorm": "nonparam_ln"}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(bench_dir: Path = BENCH_DIR) -> dict:
    """``BENCHMARK.json`` at the root of the checkout that holds ``bench_dir``."""
    return load_json(bench_dir.parent / "BENCHMARK.json")


def load_cell(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The cell file, with its name and its configuration file added."""
    path = bench_dir / "workloads" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no cell {name!r}: {path} does not exist")
    cell = load_json(path)
    cell["name"] = name
    cell["config_file"] = load_json(bench_dir / "configs" / f"{cell['config']}.json")
    cell["config_file"]["name"] = cell["config"]
    return cell


def load_module(kind: str, name: str, bench_dir: Path = BENCH_DIR):
    """``bench_dir/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind[:-1]} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = load_json(bench_dir / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table['devices'])}")
    return table["devices"][device_kind]


def model_config(doc: dict):
    """The program's ``ModelConfig`` for a configuration file.

    Starts from the program's own published configuration (``program_arch``)
    and sets every key the file gives.  A key that the file does not list
    under ``reduced`` must already equal the program's value, so the file
    says what is run and the program's registry cannot drift from it; or
    the file names the registry's value under ``program_registry``, where
    the registry departs from the published configuration and the run
    follows the published one.
    """
    from repro.configs import get_config
    base = get_config(doc["program_arch"])
    changes = {}
    for key, field in CONFIG_KEYS.items():
        if key in doc:
            changes[field] = doc[key]
    if "layer_norm" in doc:
        changes["norm"] = NORMS[doc["layer_norm"]]
    registry = doc.get("program_registry", {})
    for key, field in CONFIG_KEYS.items():
        if key in doc and key not in doc.get("reduced", []):
            if getattr(base, field) not in (doc[key], registry.get(key, doc[key])):
                raise ValueError(
                    f"{doc.get('name')}: {key}={doc[key]} but the program's "
                    f"{doc['program_arch']} has {field}={getattr(base, field)}"
                    f" and {key} is not listed under reduced")
    return base.scaled(**changes)
