"""The control and the faults that the comparison has to reject, as hooks
on the program's pure train step (``harness.run(step_hook=...)``).

They serve the readings behind each limit (``bench/calibrate.py``, on the
chip) and the tests (``tests/bench``); the benchmark's own runs never use
them. Each hook takes the program's step ``fn`` and the cell's mesh and
returns a step with the same signature.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bench.configs.spikingformer_reference import make_step


def control(model: dict):
    """The reference in the program's place, computed in bfloat16 (the
    precision below the configuration's float32)."""
    return lambda fn, mesh: make_step(model, jnp.bfloat16)


def unchanged(fn, mesh):
    """A step that returns its state unchanged (the loss still computed)."""
    def step(params, state, opt, images, labels):
        metrics = fn(params, state, opt, images, labels)[3]
        return params, state, opt, metrics
    return step


def half_batch(fn, mesh):
    """Half of the batch left out: the step sees the first half only, so
    its statistics and means are over the rest."""
    def step(params, state, opt, images, labels):
        half = images.shape[0] // 2
        return fn(params, state, opt, images[:half], labels[:half])
    return step


def no_exchange(model: dict):
    """The exchange between chips left out: the reference step run on each
    chip's shard of the batch alone (BN statistics, loss and gradient of
    the local rows, nothing reduced), every chip keeping its own update."""
    def hook(fn, mesh):
        local = make_step(model)
        rep = P()
        return jax.shard_map(
            local, mesh=mesh, in_specs=(rep, rep, rep, P("data"), P("data")),
            out_specs=(rep, rep, rep, rep), check_vma=False)
    return hook
