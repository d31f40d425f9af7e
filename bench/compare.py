"""How ``correct`` is decided: four readings of a train step's first steps,
taken the same way from the program and from the reference.

At random initialisation the Spikingformer is chaotic: a rounding step that
moves one membrane potential across its threshold flips spikes that flip
more, through 8 blocks and T steps, so two sound programs that round
differently disagree on late and single quantities (PERF.md, Findings). The
numbers compared are the ones that stay steady from seed to seed while the
control and the faults move them:

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
* ``stats_gap``: the batch statistics (mean and variance) of every
  tokenizer stage's BatchNorm, read back from the running statistics after
  the first step; per statistic, the norm of the difference over the
  reference's norm; the worst.

A depth-stacked leaf (the ``blocks`` subtree) counts as one leaf per layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import leaf_name

FIRST_STEPS = 3
TINY_GRAD = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "change_gap", "stats_gap")


def _leaf_norms(tree) -> list:
    out = []
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = a.astype(jnp.float32)
        if leaf_name(path).startswith("blocks/"):
            out.append(jnp.sqrt(jnp.sum(jnp.square(a),
                                        axis=tuple(range(1, a.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(a)))[None])
    return out


@jax.jit
def leaf_norms(tree):
    return _leaf_norms(tree)


@jax.jit
def change_norms(new, old):
    return _leaf_norms(jax.tree.map(jnp.subtract, new, old))


def _flat(norms) -> np.ndarray:
    return np.concatenate([np.asarray(n, np.float64) for n in norms])


@jax.jit
def _batch_stats(state, momentum):
    """The batch's BN statistics, recovered from running statistics one
    step after their start (mean 0, variance 1): per BN leaf and layer."""
    out = []
    for path, a in jax.tree_util.tree_flatten_with_path(state)[0]:
        start = 1.0 if leaf_name(path).endswith("var") else 0.0
        a = (a.astype(jnp.float32) - momentum * start) / (1 - momentum)
        out.append(a if a.ndim > 1 else a[None])
    return out


def first_steps(step, params, state, opt, batches, beta1: float,
                momentum: float, start):
    """Drive ``step`` through ``batches`` from (params, state, opt).

    ``step`` takes placed batches; ``start()`` regenerates the seeded
    starting parameters (the running ones are donated). Returns the
    readings and the state after the last step, to hand on."""
    losses, first = [], {}
    for i, (images, labels) in enumerate(batches):
        params, state, opt, metrics = step(params, state, opt, images, labels)
        losses.append(float(metrics["loss"]))
        if float(metrics["nonfinite"]) > 0:
            losses[-1] = float("nan")
        if i == 0:
            first["grad"] = _flat(leaf_norms(opt["m"])) / (1 - beta1)
            first["stats"] = [np.asarray(x, np.float64) for stat in
                              _batch_stats(state["tokenizer"], momentum)
                              for x in stat]
    p0 = start()
    change = _flat(change_norms(params, p0))
    del p0
    return {"losses": losses, "change": change, **first}, \
        (params, state, opt)


def _gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    return np.abs(got - want) / np.maximum(want, np.median(want))


def gaps(got: dict, want: dict) -> dict[str, float]:
    """The numbers compared, program (``got``) against reference."""
    keep = want["grad"] >= TINY_GRAD * np.median(want["grad"])
    stats = [np.linalg.norm(a - b) / np.linalg.norm(b)
             for a, b in zip(got["stats"], want["stats"])]
    finite = np.all(np.isfinite(got["losses"]))
    return {"loss_gap": abs(got["losses"][0] - want["losses"][0])
            if finite else float("nan"),
            "grad_gap": float(np.median(_gaps(got["grad"], want["grad"]))),
            "change_gap": float(np.median(_gaps(got["change"],
                                                want["change"])[keep])),
            "stats_gap": float(np.max(stats))}


def judge(numbers: dict[str, float], limits: dict[str, float]) -> bool:
    """Every number the cell compares (those ``limits`` holds) finite and
    at or under its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
