"""The control and the faults that the comparison has to reject, as hooks
on the program's pure train step (``harness.run(step_hook=...)``).

They serve the readings behind each limit (``bench/calibrate.py``, on the
chip) and the tests (``tests/bench``); the benchmark's own runs never use
them. Each hook takes the program's step ``fn`` and the cell's mesh and
returns a step with the same signature, ``(params, state, opt, *inputs)``,
whose inputs all lead with the batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def control(cell):
    """The reference in the program's place, computed in bfloat16 (the
    precision below the configuration's float32)."""
    return lambda fn, mesh: cell.family.reference_step(cell.model,
                                                       jnp.bfloat16)


def unchanged(fn, mesh):
    """A step that returns its state unchanged (the loss still computed)."""
    def step(params, state, opt, *inputs):
        metrics = fn(params, state, opt, *inputs)[3]
        return params, state, opt, metrics
    return step


def half_batch(fn, mesh):
    """Half of the batch left out: the step sees the first half only, so
    its statistics and means are over the rest."""
    def step(params, state, opt, *inputs):
        half = inputs[0].shape[0] // 2
        return fn(params, state, opt, *(x[:half] for x in inputs))
    return step


def no_exchange(cell):
    """The exchange between chips left out: the reference step run on each
    chip's shard of the batch alone (statistics, loss and gradient of the
    local rows, nothing reduced), every chip keeping its own update."""
    def hook(fn, mesh):
        local = cell.family.reference_step(cell.model)
        rep = P()

        def step(*args):
            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(rep, rep, rep) + (P("data"),) * (len(args) - 3),
                out_specs=(rep, rep, rep, rep), check_vma=False)(*args)
        return step
    return hook
