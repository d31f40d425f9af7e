"""How ``correct`` is decided: four readings of a train step's first steps,
taken the same way from the program and from the reference.

At random initialisation a deep spiking network is chaotic: a rounding
step that moves one membrane potential across its threshold flips spikes
that flip more, through every layer and time step, so two sound programs
that round differently disagree on late and single quantities (PERF.md,
Findings). The numbers compared are the ones that stay steady from seed
to seed while the control and the faults move them:

* ``loss_gap``: |loss - reference loss| of the first step, in nats.
* ``grad_gap``: the first gradient as the optimizer got it, read back from
  its first moment after one step (m / (1 - beta1)). Per leaf, the gap
  between the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf; the median leaf.
* ``change_gap``: the same gap for the parameters' change after the first
  :data:`FIRST_STEPS` steps (what the next step receives, less the seeded
  start); the median leaf. Leaves whose reference gradient is under
  :data:`TINY_GRAD` of the median leaf's move by round-off alone under Adam
  and are left out.
* ``stats_gap``: the batch statistics that the model family reads back
  from its state after the first step (its ``stats``); per statistic, the
  norm of the difference over the reference's norm; the worst. Not read
  for a family that has none.

A leaf that the family calls depth-stacked (its ``depth_stacked``) counts
as one leaf per layer. Every family's optimizer state holds its first
moment as ``opt["m"]``, and its configuration the optimizer's ``beta1``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import leaf_name

FIRST_STEPS = 3
TINY_GRAD = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "change_gap", "stats_gap")


def _leaf_norms(tree, stacked) -> list:
    out = []
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = a.astype(jnp.float32)
        if stacked(leaf_name(path)):
            out.append(jnp.sqrt(jnp.sum(jnp.square(a),
                                        axis=tuple(range(1, a.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(a)))[None])
    return out


@functools.partial(jax.jit, static_argnums=1)
def leaf_norms(tree, stacked):
    return _leaf_norms(tree, stacked)


@functools.partial(jax.jit, static_argnums=2)
def change_norms(new, old, stacked):
    return _leaf_norms(jax.tree.map(jnp.subtract, new, old), stacked)


def _flat(norms) -> np.ndarray:
    return np.concatenate([np.asarray(n, np.float64) for n in norms])


def first_steps(step, params, state, opt, batches, cell, start):
    """Drive ``step`` through ``batches`` (each a tuple of placed inputs)
    from (params, state, opt).

    ``start()`` regenerates the seeded starting parameters (the running
    ones are donated); ``cell`` gives the model family and its
    configuration. Returns the readings and the state after the last
    step, to hand on."""
    family, model = cell.family, cell.model
    beta1 = model["optimizer"]["beta1"]
    losses, first = [], {}
    for i, inputs in enumerate(batches):
        params, state, opt, metrics = step(params, state, opt, *inputs)
        losses.append(float(metrics["loss"]))
        if float(metrics["nonfinite"]) > 0:
            losses[-1] = float("nan")
        if i == 0:
            first["grad"] = _flat(leaf_norms(
                opt["m"], family.depth_stacked)) / (1 - beta1)
            stats = family.stats(state, model)
            if stats is not None:
                first["stats"] = [np.asarray(x, np.float64)
                                  for stat in stats for x in stat]
    p0 = start()
    change = _flat(change_norms(params, p0, family.depth_stacked))
    del p0
    return {"losses": losses, "change": change, **first}, \
        (params, state, opt)


def _gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    return np.abs(got - want) / np.maximum(want, np.median(want))


def gaps(got: dict, want: dict) -> dict[str, float]:
    """The numbers compared, program (``got``) against reference; no
    ``stats_gap`` where the family reads no statistics."""
    keep = want["grad"] >= TINY_GRAD * np.median(want["grad"])
    finite = np.all(np.isfinite(got["losses"]))
    out = {"loss_gap": abs(got["losses"][0] - want["losses"][0])
           if finite else float("nan"),
           "grad_gap": float(np.median(_gaps(got["grad"], want["grad"]))),
           "change_gap": float(np.median(_gaps(got["change"],
                                               want["change"])[keep]))}
    if "stats" in want:
        out["stats_gap"] = float(np.max(
            [np.linalg.norm(a - b) / np.linalg.norm(b)
             for a, b in zip(got["stats"], want["stats"])]))
    return out


def judge(numbers: dict[str, float], limits: dict[str, float]) -> bool:
    """Every number the cell compares (those ``limits`` holds) read,
    finite and at or under its limit."""
    return all(k in numbers and np.isfinite(numbers[k])
               and numbers[k] <= limits[k] for k in limits)
