"""Finding a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files are found by those names:

* ``bench/configs/<config>.json`` -- the model's sizes and settings;
* ``bench/traffic/<traffic>.json`` -- the job: batch, mesh, policy, images;
* ``bench/limits/<cell>.json`` -- the limits on the numbers compared;
* ``bench/metrics/<metric>.py`` -- the reader of each per-layer metric.

Nothing here names a cell: a new cell is new files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    model: dict            # the configuration file
    mix: dict              # the traffic mix file
    limits: dict           # number -> limit
    end_to_end: tuple      # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files loaded;
    ``KeyError`` naming the cells there if it has none of that name."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    here = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        model=load_json(here / "configs" / f"{w['config']}.json"),
        mix=load_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(here / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
