"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; each
lives in a file of its own, found by name:

    bench/configs/<config>.json    model sizes, as run, beside the source's
    bench/traffic/<traffic>.json   jobs, document lengths, chips, sources
    bench/limits/<cell>.json       the limits of the correctness check
    bench/metrics/<metric>.py      one reader per per-layer metric

A cell is added by adding files: nothing here names a cell.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

# source config.json key -> ModelConfig field, for the dense GQA family
_HF_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # bench/configs/<config>.json
    traffic: Dict[str, Any]         # bench/traffic/<traffic>.json
    limits: Dict[str, float]        # bench/limits/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str, bench_dir: Optional[str] = None) -> Cell:
    """The cell *name* of ``<root>/BENCHMARK.json`` with its files, read
    from *bench_dir* (default: this directory)."""
    bench_dir = bench_dir or BENCH_DIR
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = _read_json(os.path.join(bench_dir, "configs",
                                     w["config"] + ".json"))
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    limits = _read_json(os.path.join(bench_dir, "limits", name + ".json"))
    if int(traffic["chips"]) != int(w["chips"]):
        raise ValueError(f"{name}: BENCHMARK.json asks for {w['chips']} "
                         f"chips, traffic {w['traffic']} for "
                         f"{traffic['chips']}")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def model_config(cell: Cell):
    """The program's ``ModelConfig`` for the cell: the registry entry the
    configuration file names, with every size the file states."""
    from repro.configs import get_config
    m = cell.model
    kw = {field: m[key] for key, field in _HF_KEYS.items() if key in m}
    kw["head_dim"] = m.get("head_dim", m["hidden_size"]
                           // m["num_attention_heads"])
    kw["attn_bias"] = bool(m["qkv_bias"])
    # text-only training of the language model: no modality frontend
    kw.update(family="dense", frontend_dim=0, num_patches=0,
              name=cell.config["name"])
    return dataclasses.replace(get_config(cell.config["registry"]), **kw)


def metric_reader(name: str, bench_dir: Optional[str] = None
                  ) -> Callable[[Any], Optional[float]]:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(bench_dir or BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
