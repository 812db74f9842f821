"""Find a cell's parts by name: everything a cell is made of is data.

``BENCHMARK.json`` names the cells (``workloads``), the configurations
(each with its ``file``) and the metrics.  For a cell the harness then
reads, each from a file of its own:

* the configuration: ``configs[].file`` (grid, dtype, taps, stencil).
  A configuration for more than one chip also has ``mesh``, the shards
  per spatial axis (``[2, 2]``: the field split in halves along both
  axes), whose product is the cell's ``chips``; the harness makes the
  field in those shards and checks it shard by shard.  A configuration
  without ``mesh`` is a one-device one;
* the traffic mix: ``traffic/<traffic>.json`` beside this file;
* the limits of the output comparison: ``limits/<workload>.json``;
* each per-layer metric: a reader module ``metrics/<name>.py`` with a
  ``read(ctx)`` function that returns a number, or None where it finds
  nothing to read.

A new cell or metric therefore needs new files and a new entry in
``BENCHMARK.json``, and no edit to any file here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: tuple[dict, ...]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str, bench_dir: Path = HERE) -> Cell:
    """Resolve ``workload`` through ``root/BENCHMARK.json``; the traffic,
    limits and metric files are looked up under ``bench_dir``."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = load_json(bench_dir / "limits" / f"{workload}.json")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic, limits=limits,
        per_layer=tuple(m for m in spec["per_layer"]
                        if "workloads" not in m
                        or workload in m["workloads"]))


def metric_reader(name: str, bench_dir: Path = HERE) -> ModuleType:
    """Import ``metrics/<name>.py``; it defines ``read(ctx)``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
