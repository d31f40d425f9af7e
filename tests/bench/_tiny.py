"""Cells of the benchmark cut to a size a CPU test run can hold."""
import copy
import dataclasses
import time

from bench import cells, harness
from bench.cells import BENCH, Cell, load_json

TINY = dict(num_layers=2, d_model=64, n_heads=2, d_ff=128, time_steps=2,
            image_size=32, patch_grid=8, num_classes=10)


def tiny(cell: Cell, batch: int = 8) -> Cell:
    model = copy.deepcopy(cell.model)
    model["model"].update(TINY)
    return dataclasses.replace(cell, model=model,
                               mix=dict(cell.mix, batch=batch))


def tiny_cell(name: str, batch: int = 8) -> Cell:
    """The benchmark's cell ``name``, cut to the tiny size, with its limits."""
    return tiny(cells.find_cell(name), batch)


def run(cell: Cell, devices, seed=5, trace=False, hook=None, seconds=0.3):
    """Everything of a run but the look for a chip."""
    peaks = load_json(BENCH / "peaks.json")["TPU v5 lite"]
    return harness.run(cell, seed, seconds, trace, devices, peaks,
                       time.perf_counter(), step_hook=hook)


def step_flops(cell: Cell, batch: int) -> float:
    """The model FLOPs of one step of ``cell``'s configuration."""
    return cell.family.step_flops(cell.model, batch)


def kernel_work(kind: str, cell: Cell, batch: int, plan: dict) -> list:
    """(flops, bytes) of each piece of kernel family ``kind``'s work."""
    return cell.family.kernel_work(kind, cell.model, batch, plan)
