"""Finding a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files are found by those names:

* ``bench/configs/<config>.json`` -- the model's sizes and settings, and
  the name of its family;
* ``bench/families/<family>.py`` -- what depends on the model family: the
  program's step, the batches, the initialisation, the reference, the
  statistics compared and the counts of work;
* ``bench/traffic/<traffic>.json`` -- the job: batch, mesh and what the
  family's generator reads;
* ``bench/limits/<cell>.json`` -- the limits on the numbers compared;
* ``bench/metrics/<metric>.py`` -- the reader of each per-layer metric.

Nothing here names a cell or a family: a new one is new files and entries.
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
    family: object         # the module bench/families/<family>.py


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def family(name: str, root: Path = ROOT):
    """The module ``bench/families/<name>.py`` of ``root``; ``KeyError``
    naming the families there if it has none of that name."""
    here = root / "bench" / "families"
    known = sorted(p.stem for p in here.glob("*.py"))
    if name not in known:
        raise KeyError(f"no model family {name!r}; bench/families has "
                       f"{known}")
    return _load(here / f"{name}.py", f"bench_family_{name}")


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files loaded;
    ``KeyError`` naming the cells there if it has none of that name, or
    naming the families if its configuration names none of them."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    here = root / "bench"
    model = load_json(here / "configs" / f"{w['config']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), model=model,
        mix=load_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(here / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        family=family(model.get("family"), root))


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    return _load(root / "bench" / "metrics" / f"{name}.py",
                 f"bench_metric_{name.replace('.', '_')}").read
