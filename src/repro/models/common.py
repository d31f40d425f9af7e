"""Shared model-building utilities: augmented param trees (array + sharding
spec defined at a single point), norms, RoPE, embeddings, and the
sharding-constraint helper used throughout the substrate.

Convention: every ``init_*`` returns a pytree whose leaves are ``Leaf``
(array, PartitionSpec) pairs; ``split_tree`` separates them into the params
tree handed to jit and the matching spec tree used for ``in_shardings``.
Mesh axes: batch shards over ("pod", "data") (the pod axis exists only on
the multi-pod mesh and is ignored otherwise); tensor parallel over "model".
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# Logical -> physical axis naming. "batch" maps to every data-like mesh axis
# present; "model" is tensor-parallel. Specs below use the physical names
# directly; the pod axis is folded into batch at constraint time.
BATCH = ("pod", "data")
MODEL = "model"


@dataclasses.dataclass
class Leaf:
    """A parameter leaf: the array plus its partition spec."""

    value: jax.Array
    spec: P

    def tree_flatten(self):  # pragma: no cover - not registered; plain leaf
        raise NotImplementedError


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def spec_is_leaf(x) -> bool:
    """Pytree leaf predicate for PartitionSpec trees: a spec leaf is a
    ``PartitionSpec`` or ``None`` (replicated). Shared by every spec-tree
    transform so the definition cannot drift between copies."""
    return isinstance(x, P) or x is None


def split_tree(aug: Any) -> tuple[Any, Any]:
    """Augmented tree -> (params, specs)."""
    params = jax.tree.map(lambda l: l.value, aug, is_leaf=is_leaf)
    specs = jax.tree.map(lambda l: l.spec, aug, is_leaf=is_leaf)
    return params, specs


def stack_layer_trees(augs: list[Any]) -> Any:
    """Stack per-layer augmented trees along a new leading (scan) axis; the
    layer axis is unsharded (it is scanned, never partitioned)."""
    def stack(*leaves: Leaf) -> Leaf:
        arr = jnp.stack([l.value for l in leaves])
        return Leaf(arr, P(None, *leaves[0].spec))
    return jax.tree.map(stack, *augs, is_leaf=is_leaf)


def _ambient_mesh():
    """The mesh activated by ``jax.set_mesh`` for sharding-constraint
    resolution, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def shard(x: jax.Array, *spec) -> jax.Array:
    """Sharding constraint that no-ops when no mesh is in context (so the
    same model code runs in single-device tests and under the prod mesh).
    Axis names absent from the context mesh are dropped from the spec, as
    are axes whose dim does not divide evenly (uneven GSPMD shardings
    round-trip poorly)."""
    mesh = _ambient_mesh()
    names = set(mesh.axis_names) if mesh is not None else set()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes)) if names else {}

    def keep(ax, dim):
        if ax is None:
            return None
        axes = ax if isinstance(ax, tuple) else (ax,)
        axes = tuple(a for a in axes if a in names)
        total = 1
        for a in axes:
            total *= sizes[a]
        if not axes or dim % total != 0:
            return None
        return axes if len(axes) > 1 else axes[0]

    if not names:
        return x
    try:
        fixed = [keep(s, d) for s, d in zip(spec, x.shape)]
        # Fallback relocation: an axis dropped for non-divisibility (e.g.
        # 20 heads on 16 shards) moves to the rightmost free divisible dim
        # (usually head_dim) instead of silently replicating the tensor —
        # a replicated activation costs a full mesh-width of redundant work.
        in_use = {a for f in fixed if f is not None
                  for a in ((f,) if not isinstance(f, tuple) else f)}
        for ax, f in zip(spec, fixed):
            if ax is None or f is not None:
                continue
            axes = tuple(a for a in (ax if isinstance(ax, tuple) else (ax,))
                         if a in names and a not in in_use)
            if not axes:
                continue
            total = 1
            for a in axes:
                total *= sizes[a]
            for i in range(len(fixed) - 1, -1, -1):
                if fixed[i] is None and x.shape[i] % total == 0 and \
                        x.shape[i] >= total:
                    fixed[i] = axes if len(axes) > 1 else axes[0]
                    in_use.update(axes)
                    break
        return jax.lax.with_sharding_constraint(x, P(*fixed))
    except Exception:
        return x


def shard_batch(x: jax.Array, *rest) -> jax.Array:
    """Constrain the leading dim over the (pod, data) batch axes."""
    return shard(x, BATCH, *rest)


def mesh_axis_size(name: str) -> int | None:
    """Size of a mesh axis in the ambient (trace-time) mesh, else None."""
    try:
        mesh = _ambient_mesh()
        if mesh is None:
            return None
        sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
        return sizes.get(name)
    except Exception:
        return None


def lscan(cfg, f, init, xs):
    """lax.scan honoring cfg.scan_unroll (the dry-run's marginal-layer
    costing unrolls small-depth variants so cost_analysis sees every layer)."""
    unroll = True if getattr(cfg, "scan_unroll", False) else 1
    return jax.lax.scan(f, init, xs, unroll=unroll)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def normal_leaf(key, shape, spec: tuple, scale: float | None = None,
                dtype=jnp.float32) -> Leaf:
    scale = shape[-2] ** -0.5 if scale is None and len(shape) >= 2 else \
        (scale if scale is not None else 0.02)
    return Leaf(jax.random.normal(key, shape, dtype) * scale, P(*spec))


def zeros_leaf(shape, spec: tuple, dtype=jnp.float32) -> Leaf:
    return Leaf(jnp.zeros(shape, dtype), P(*spec))


def ones_leaf(shape, spec: tuple, dtype=jnp.float32) -> Leaf:
    return Leaf(jnp.ones(shape, dtype), P(*spec))


def full_leaf(shape, value: float, spec: tuple, dtype=jnp.float32) -> Leaf:
    return Leaf(jnp.full(shape, value, dtype), P(*spec))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(dim: int, dtype=jnp.float32):
    return {"scale": ones_leaf((dim,), (None,), dtype)}


def rmsnorm(params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(dim: int, dtype=jnp.float32):
    return {"scale": ones_leaf((dim,), (None,), dtype),
            "bias": zeros_leaf((dim,), (None,), dtype)}


def layernorm(params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(
        jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    d_head = x.shape[-1]
    freqs = rope_frequencies(d_head, theta)                    # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, ·)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32):
    return {"table": normal_leaf(key, (vocab, d_model), (MODEL, None),
                                 scale=0.02, dtype=dtype)}


def embed(params, tokens: jax.Array, dtype=None) -> jax.Array:
    t = params["table"]
    out = jnp.take(t, tokens, axis=0)
    return out.astype(dtype) if dtype is not None else out


def unembed(params, x: jax.Array) -> jax.Array:
    """(..., D) -> (..., V) logits, fp32 for a stable softmax."""
    logits = jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                        params["table"].astype(jnp.float32))
    return shard_batch(logits, *([None] * (logits.ndim - 2)), MODEL)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: jax.Array | None = None) -> jax.Array:
    """logits (B, S, V) fp32; labels (B, S) int32; mask optional (B, S)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
