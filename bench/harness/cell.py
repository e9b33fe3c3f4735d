"""Resolve a cell of BENCHMARK.json to its files, by name.

A cell (`workloads` entry) names a configuration and a traffic mix.
Everything that belongs to one of them, or to one per-layer metric, is
a file of its own under bench/, found from the name alone:

  bench/configs/<config>.json   sizes as run, source, cut, reference
  bench/reference/<ref>.py      the configuration's plain reference:
                                param_specs, loss, step_flops
  bench/traffic/<traffic>.json  clients, steps, batch, sequence, codec
  bench/limits/<cell>.json      each compared number's limit, readings
  bench/metrics/<metric>.py     per-layer reader: read(record) -> value

so a later cell or metric is added by adding files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file
    traffic: Dict[str, Any]         # the traffic file
    limits: Dict[str, Any]          # the limits file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]


def load_benchmark() -> Dict[str, Any]:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: Path) -> Dict[str, Any]:
    return json.loads(path.read_text())


def load_cell(name: str) -> Cell:
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / cfgs[w["config"]]["file"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(BENCH_DIR / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(cell: Cell):
    ref = cell.config["reference"]
    return load_module(BENCH_DIR / "reference" / f"{ref}.py",
                       f"reference.{ref}")


def metric_reader(name: str) -> Callable:
    mod = load_module(BENCH_DIR / "metrics" / f"{name}.py",
                      f"bench_metric_{name.replace('.', '_')}")
    return mod.read
