"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; each
lives in a file of its own, found by name:

    bench/configs/<config>.json    model sizes, as run, beside the source's,
                                   and the model family
    bench/families/<family>.py     the family's weights, FLOPs, reference
    bench/traffic/<traffic>.json   jobs, document lengths, chips, sources
    bench/limits/<cell>.json       the limits of the correctness check
    bench/metrics/<metric>.py      one reader per per-layer metric

A cell is added by adding files: nothing here names a cell.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


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

    @property
    def family(self):
        return family(self.config)


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


def families() -> List[str]:
    """The model families the benchmark knows: ``families/<name>.py``."""
    return sorted(f[:-3] for f in os.listdir(os.path.join(BENCH_DIR,
                                                          "families"))
                  if f.endswith(".py") and not f.startswith("_"))


def family(config: Dict[str, Any]):
    """The family module a configuration file names by ``"family"``:
    its weights, adapted projections, FLOPs and reference."""
    name = config.get("family")
    if name not in families():
        raise ValueError(f"configuration {config.get('name')!r} names "
                         f"family {name!r}; known families: {families()}")
    return importlib.import_module("families." + name)


def model_config(cell: Cell):
    """The program's ``ModelConfig`` for the cell: the registry entry the
    configuration file names, with every size the file states."""
    from repro.configs import get_config
    kw = cell.family.model_fields(cell.model)
    # text-only training of the language model: no modality frontend
    kw.update(frontend_dim=0, num_patches=0, name=cell.config["name"])
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
