"""Production mesh construction (never touches jax device state at import).

Single pod : (data=16, model=16)           = 256 chips
Multi-pod  : (pod=2, data=16, model=16)    = 512 chips

The pod axis is an extra pure-data-parallel dimension (gradients all-reduce
across pods over DCN); batch shards over ("pod", "data").
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.models.common import spec_is_leaf


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def use_mesh(mesh):
    """Context manager activating ``mesh`` for sharding-constraint
    resolution (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1, devices=None):
    """Small (data, model) mesh: over all devices, or over ``devices``
    (1 device => (1, 1))."""
    return _make_mesh((data, model), ("data", "model"), devices)


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def apply_fsdp(specs, shapes, mesh, min_elems: int = 1 << 20,
               axis: str = "data", scan_dims=None):
    """ZeRO-3-style weight sharding: every large leaf gets one extra free dim
    sharded over the data axis (XLA all-gathers it just-in-time per layer).
    Cuts parameter + Adam-moment residency by the data-axis size.

    ``scan_dims`` (optional) is a pytree of ints matching ``specs``: the
    number of leading scan/vmap dims per leaf that must never be sharded —
    the Spikingformer's stacked block leaves carry a leading L (depth) axis
    that the model splits into per-block leaves locally, and sharding it
    would turn that split into a gather."""
    if axis not in mesh.axis_names:
        return specs
    size = dict(zip(mesh.axis_names, mesh.axis_sizes))[axis]

    def fix(spec, leaf, n_scan=0):
        import numpy as np
        shape = leaf.shape
        if spec is None or int(np.prod(shape)) < min_elems:
            return spec
        cur = list(spec) + [None] * (len(shape) - len(spec))
        used = {a for s in cur if s is not None
                for a in ((s,) if not isinstance(s, tuple) else s)}
        if axis in used:
            return spec
        # choose the largest unsharded, divisible dim
        best, best_dim = -1, -1
        for i, (ax, d) in enumerate(zip(cur, shape)):
            if i >= n_scan and ax is None and d % size == 0 and d > best:
                best, best_dim = d, i
        if best_dim < 0:
            return spec
        cur[best_dim] = axis
        return P(*cur)

    if scan_dims is None:
        return jax.tree.map(fix, specs, shapes, is_leaf=spec_is_leaf)
    return jax.tree.map(fix, specs, shapes, scan_dims, is_leaf=spec_is_leaf)


def sanitize_specs(specs, shapes, mesh):
    """Drop sharding on dims that do not divide evenly and on axes missing
    from the mesh; a dropped axis relocates to the rightmost free divisible
    dim of the same tensor (e.g. 20 attention heads on 16 shards fall back
    to head-dim parallelism instead of replicating the projection)."""
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))

    def norm(ax):
        axes = ax if isinstance(ax, tuple) else (ax,)
        axes = tuple(a for a in axes if a in sizes)
        total = 1
        for a in axes:
            total *= sizes[a]
        return axes, total

    def fix(spec, shape):
        if spec is None:
            return None
        out, dropped = [], []
        for i, ax in enumerate(spec):
            if ax is None:
                out.append(None)
                continue
            axes, total = norm(ax)
            if not axes or i >= len(shape) or shape[i] % total != 0:
                out.append(None)
                dropped.append(ax)
            else:
                out.append(axes if len(axes) > 1 else axes[0])
        in_use = {a for f in out if f is not None
                  for a in ((f,) if not isinstance(f, tuple) else f)}
        for ax in dropped:
            axes, total = norm(ax)
            axes = tuple(a for a in axes if a not in in_use)
            if not axes:
                continue
            total = 1
            for a in axes:
                total *= sizes[a]
            for i in range(len(out) - 1, -1, -1):
                if out[i] is None and i < len(shape) and \
                        shape[i] % total == 0 and shape[i] >= total:
                    out[i] = axes if len(axes) > 1 else axes[0]
                    in_use.update(axes)
                    break
        return P(*out)

    return jax.tree.map(
        lambda s, sh: fix(s, sh.shape),
        specs, shapes, is_leaf=spec_is_leaf)
