"""Seeded weights in the program's parameter layout, made on the device.

The benchmark, not the program, draws the weights, so that the program
and the reference start from the same numbers without the reference
taking anything the program made. Each leaf is drawn from its own key
(the seed folded with the leaf's index), by the model family's ``init``
rule for the leaf's name.
"""
from __future__ import annotations

import jax


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also one over 32 bits."""
    s = seed % 2**64
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def make_params(struct, seed: int, init):
    """Parameters shaped like ``struct`` (a pytree of arrays or
    ``ShapeDtypeStruct``), drawn from ``seed`` by ``init(name, shape,
    dtype, key)``, in ``struct``'s shardings where it has them. One jitted
    call."""
    flat, tree = jax.tree_util.tree_flatten_with_path(struct)
    shardings = [getattr(a, "sharding", None) for _, a in flat]

    def make(key):
        return jax.tree_util.tree_unflatten(tree, [
            init(leaf_name(path), a.shape, a.dtype,
                 jax.random.fold_in(key, i))
            for i, (path, a) in enumerate(flat)])

    out = (jax.tree_util.tree_unflatten(tree, shardings)
           if all(s is not None for s in shardings) else None)
    return jax.jit(make, out_shardings=out)(seed_key(seed))
