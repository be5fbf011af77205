"""What ``BENCHMARK.json`` names, resolved to the files that hold it.

Every configuration, traffic mix, cell and per-layer metric lives in a file
of its own, found by its name:

* ``chipbench/configs/<config>.json``  model sizes, serving sizes, source,
  and the architecture module that reads them (``chipbench/arch``);
* ``chipbench/traffic/<traffic>.json`` parameters of one traffic mix;
* ``chipbench/cells/<workload>.json``  what belongs to one cell alone
  (its offered rate, its correctness limit);
* ``chipbench/metrics/<metric>.py``    the reader of one per-layer metric.

Nothing here imports the program under test.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

from chipbench import arch

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
BENCHMARK_JSON = ROOT / "BENCHMARK.json"


def model_spec(conf: dict):
    """The spec of a configuration file, made by the architecture module
    its ``"architecture"`` key names."""
    return arch.load(conf.get("architecture")).model_spec(conf)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(path: Path = BENCHMARK_JSON) -> dict:
    return load_json(path)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with every file it names loaded."""

    name: str
    entry: dict              # the workload's entry
    config: dict             # the configuration file
    arch: ModuleType         # the architecture module it names
    spec: object             # made by that module from the file
    traffic: dict            # the traffic mix's file
    data: dict               # the cell's own file
    end_to_end: list[dict]   # metrics this cell reports with --trace 0
    per_layer: list[dict]    # metrics this cell reports with --trace 1


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(cell_name: str, bench: dict | None = None,
            base: Path = BENCH_DIR) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == cell_name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
    config = load_json(ROOT / conf_entry["file"])
    mod = arch.load(config.get("architecture"), base / "arch")
    e2e = [m for m in bench["end_to_end"] if _reports(m, cell_name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in e2e_names and _reports(m, cell_name)]
    return Cell(name=cell_name, entry=entry, config=config, arch=mod,
                spec=mod.model_spec(config),
                traffic=load_json(base / "traffic" / f"{entry['traffic']}.json"),
                data=load_json(base / "cells" / f"{cell_name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, base: Path = BENCH_DIR):
    """The ``read(run) -> float | None`` function of one per-layer metric,
    loaded by path (metric names hold dots)."""
    path = base / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
