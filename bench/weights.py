"""Seeded weights in the program's parameter layout, made on the device.

The benchmark, not the program, draws the weights, so that the program
and the reference start from the same numbers without the reference
taking anything the program made. Each leaf is drawn from its own key
(the seed folded with the leaf's index), by the rule the configuration
file's ``init`` states for the leaf's name.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also one over 32 bits."""
    s = seed % 2**64
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _draw(name: str, shape, dtype, key):
    last = name.rsplit("/", 2)
    if last[-1] in ("gamma",):
        return jnp.ones(shape, dtype)
    if last[-1] in ("beta",) or name == "head/b":
        return jnp.zeros(shape, dtype)
    if name.endswith("conv/w"):
        fan_in = shape[0] * shape[1] * shape[2]
    elif name.endswith("linear/w") or name == "head/w":
        fan_in = shape[-2]
    else:
        raise ValueError(f"no init rule for parameter {name!r}")
    return jax.random.normal(key, shape, dtype) * fan_in ** -0.5


def make_params(struct, seed: int):
    """Parameters shaped like ``struct`` (a pytree of arrays or
    ``ShapeDtypeStruct``), drawn from ``seed``, in ``struct``'s shardings
    where it has them. One jitted call."""
    flat, tree = jax.tree_util.tree_flatten_with_path(struct)
    shardings = [getattr(a, "sharding", None) for _, a in flat]

    def make(key):
        return jax.tree_util.tree_unflatten(tree, [
            _draw(leaf_name(path), a.shape, a.dtype,
                  jax.random.fold_in(key, i))
            for i, (path, a) in enumerate(flat)])

    out = (jax.tree_util.tree_unflatten(tree, shardings)
           if all(s is not None for s in shardings) else None)
    return jax.jit(make, out_shardings=out)(seed_key(seed))
