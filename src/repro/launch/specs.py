"""ShapeDtypeStruct stand-ins for every (architecture x input-shape) cell.

``input_specs(cfg, shape_name)`` returns (fn, args_struct, args_specs):
the step callable to lower, the ShapeDtypeStruct pytree of its inputs, and
the matching PartitionSpec pytree — no device allocation anywhere
(params/opt-state come from ``jax.eval_shape`` over the real initializers).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.common import spec_is_leaf, split_tree

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    kind: str          # train | prefill | decode
    seq: int
    batch: int


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode", 32768, 128),
    "long_500k": ShapeSpec("decode", 524288, 1),
}


def param_structs(cfg: ArchConfig):
    """(params struct tree, spec tree) via eval_shape — zero allocation."""
    if cfg.family == "audio":
        from repro.models.encdec import init_encdec as init
    else:
        from repro.models.lm import init_lm as init

    specs_box = {}

    def build(key):
        aug = init(key, cfg)
        params, specs = split_tree(aug)
        specs_box["specs"] = specs
        return params

    structs = jax.eval_shape(build, jax.random.PRNGKey(0))
    return structs, specs_box["specs"]


def depth_scan_dims(specs):
    """Per-leaf count of leading stacked dims ``apply_fsdp`` must not
    shard: 1 for the block leaves stacked over depth (under a ``blocks``
    key), 0 elsewhere."""
    def n_scan(path, _):
        return 1 if any(getattr(p, "key", None) == "blocks" for p in path) \
            else 0
    return jax.tree_util.tree_map_with_path(
        n_scan, specs, is_leaf=spec_is_leaf)


def vision_structs(cfg, mesh, fsdp_min_elems: int = 1 << 20):
    """(params, bn-state) structs + effective mesh specs of a vision model
    (``cfg.init`` and ``cfg.param_specs``: the Spikingformer or QKFormer).

    The single source of the vision sharding plan: the model's logical
    specs are sanitized against the mesh and FSDP'd over "data" (leaves
    stacked over depth, under a ``blocks`` key, keep their leading axis
    unsharded via :func:`depth_scan_dims`). Used by
    ``launch.train.build_vision_state``, the vision dry-run cell and
    ``SpikingFormerConfig.describe_execution(mesh)``.
    """
    from repro.launch.mesh import apply_fsdp, sanitize_specs

    p_struct, s_struct = jax.eval_shape(cfg.init, jax.random.PRNGKey(0))
    p_specs, s_specs = cfg.param_specs()
    p_specs = sanitize_specs(p_specs, p_struct, mesh)
    p_specs = apply_fsdp(p_specs, p_struct, mesh, min_elems=fsdp_min_elems,
                         scan_dims=depth_scan_dims(p_specs))
    s_specs = sanitize_specs(s_specs, s_struct, mesh)
    return (p_struct, s_struct), (p_specs, s_specs)


def _vision_input_specs(cfg, sh: ShapeSpec, mesh, ba):
    """(fn, args_structs, args_specs) for a vision train cell."""
    from repro.train.loop import make_train_step
    from repro.train.optimizer import OptimizerConfig
    if sh.kind != "train":
        raise ValueError(
            f"vision cells are train-only (shape kind {sh.kind!r})")
    (p_struct, s_struct), (p_specs, s_specs) = vision_structs(cfg, mesh)
    o_struct, o_specs = opt_structs(p_struct, p_specs)
    b = sh.batch
    images = SDS((b, cfg.image_size, cfg.image_size, cfg.in_channels),
                 jnp.float32)
    labels = SDS((b,), jnp.int32)
    fn = make_train_step(cfg, OptimizerConfig(), mesh=mesh)
    return fn, (p_struct, s_struct, o_struct, images, labels), \
        (p_specs, s_specs, o_specs, P(ba or None, None, None, None),
         P(ba or None))


def opt_structs(params_struct, params_specs):
    m = jax.tree.map(lambda s: SDS(s.shape, s.dtype), params_struct)
    v = jax.tree.map(lambda s: SDS(s.shape, s.dtype), params_struct)
    state = {"m": m, "v": v, "step": SDS((), jnp.int32), "err": None}
    specs = {"m": params_specs, "v": params_specs, "step": P(), "err": None}
    return state, specs


def _batch_structs(cfg: ArchConfig, sh: ShapeSpec, batch_axes):
    b, s = sh.batch, sh.seq
    ba = batch_axes or None
    toks = SDS((b, s), jnp.int32)
    out = {"tokens": toks, "labels": SDS((b, s), jnp.int32)}
    spec = {"tokens": P(ba, None), "labels": P(ba, None)}
    if cfg.family == "audio":
        out["frames"] = SDS((b, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        spec["frames"] = P(ba, None, None)
    if cfg.vlm_stub:
        out["patch_embeds"] = SDS((b, s, cfg.d_model), jnp.bfloat16)
        out["patch_mask"] = SDS((b, s), jnp.bool_)
        spec["patch_embeds"] = P(ba, None, None)
        spec["patch_mask"] = P(ba, None)
    return out, spec


def cache_structs(cfg: ArchConfig, batch: int, max_seq: int, batch_axes):
    """Decode-state structs + specs (mirrors models.lm.init_cache)."""
    from repro.models import lm as lm_mod
    from repro.models import encdec as encdec_mod
    ba = batch_axes or None
    bspec = ba if batch > 1 else None

    if cfg.family == "audio":
        def build():
            import numpy as np
            acfg = encdec_mod._dec_attn_cfg(cfg)
            from repro.models.attention import init_kv_cache
            self_c = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None],
                                           (cfg.num_layers, *a.shape)),
                init_kv_cache(batch, acfg, max_seq, jnp.bfloat16))
            hk = cfg.n_kv_heads or cfg.n_heads
            cross = {"mk": jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq,
                                      hk, cfg.head_dim), jnp.bfloat16),
                     "mv": jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq,
                                      hk, cfg.head_dim), jnp.bfloat16)}
            return {"self": self_c, "cross": cross}
        struct = jax.eval_shape(build)
    else:
        struct = jax.eval_shape(
            lambda: lm_mod.init_cache(cfg, batch, max_seq, jnp.bfloat16))

    def spec_for(s: SDS):
        # (L, B, ...) leading layer axis unsharded; batch over data axes if
        # divisible. Model axis ("auto"): heads (dim 3 of 5D attention
        # caches) when divisible, else the sequence dim (dim 2) — matching
        # the decode compute layout so the cache is never resharded
        # per step. "trailing": naive last-dim placement (§Perf baseline).
        dims: list[Any] = [None] * len(s.shape)
        if len(s.shape) >= 2:
            dims[1] = bspec
        if cfg.cache_shard == "auto" and len(s.shape) == 5:
            order = (3, 2, 4)       # heads, seq, head_dim
        elif cfg.cache_shard == "auto" and len(s.shape) == 4:
            order = (2, 3)          # seq, feature (MLA latent / cross-mem)
        else:
            order = tuple(range(len(s.shape) - 1, 1, -1))
        for i in order:
            if i < len(s.shape) and s.shape[i] % 16 == 0 and \
                    s.shape[i] >= 16:
                dims[i] = "model"
                break
        return P(*dims)

    specs = jax.tree.map(spec_for, struct)
    return struct, specs


def input_specs(cfg: ArchConfig, shape_name: str, mesh,
                microbatches: int = 1
                ) -> tuple[Callable, tuple, tuple]:
    """Returns (fn, args_structs, args_specs) for the cell."""
    from repro.launch.mesh import apply_fsdp, batch_axes as mesh_batch_axes
    from repro.launch.mesh import sanitize_specs
    sh = SHAPES[shape_name]
    ba = mesh_batch_axes(mesh)
    if getattr(cfg, "family", None) == "vision":
        return _vision_input_specs(cfg, sh, mesh, ba)
    p_struct, p_specs = param_structs(cfg)
    p_specs = sanitize_specs(p_specs, p_struct, mesh)
    # 2D weight sharding over (data, model): always for training (ZeRO-3);
    # for serving only when TP-resident weights would overflow HBM (e.g.
    # DeepSeek-V2's 472 GB bf16 on 16-way TP) — smaller models keep weights
    # resident and avoid per-step all-gathers.
    import numpy as _np
    param_bytes = sum(int(_np.prod(s.shape)) * s.dtype.itemsize
                      for s in jax.tree.leaves(p_struct))
    m_size = mesh.devices.shape[mesh.axis_names.index("model")]
    if sh.kind == "train" or param_bytes / m_size > 8e9:
        p_specs = apply_fsdp(p_specs, p_struct, mesh)

    if sh.kind == "train":
        from repro.train.loop import make_train_step
        from repro.train.optimizer import OptimizerConfig
        o_struct, o_specs = opt_structs(p_struct, p_specs)
        b_struct, b_specs = _batch_structs(cfg, sh, ba)
        fn = make_train_step(cfg, OptimizerConfig(), microbatches)
        return fn, (p_struct, o_struct, b_struct), (p_specs, o_specs, b_specs)

    if sh.kind == "prefill":
        b_struct, b_specs = _batch_structs(cfg, sh, ba)
        if cfg.family == "audio":
            from repro.models.encdec import encode, decode_train
            from repro.models.common import unembed

            def fn(params, batch):
                enc = encode(params, batch["frames"], cfg)
                x = decode_train(params, batch["tokens"], enc, cfg)
                return unembed(params["embed"], x[:, -1])
        else:
            from repro.models.lm import lm_prefill
            fn = lambda params, batch: lm_prefill(params, batch, cfg)  # noqa
        b_struct.pop("labels"), b_specs.pop("labels")
        return fn, (p_struct, b_struct), (p_specs, b_specs)

    # decode
    c_struct, c_specs = cache_structs(cfg, sh.batch, sh.seq, ba)
    c_specs = sanitize_specs(c_specs, c_struct, mesh)
    tok = SDS((sh.batch, 1), jnp.int32)
    pos = SDS((sh.batch,), jnp.int32)
    tok_spec = P(ba if sh.batch > 1 else None, None)
    pos_spec = P(ba if sh.batch > 1 else None)
    if cfg.family == "audio":
        from repro.models.encdec import encdec_decode_step

        def fn(params, cache, tokens, pos):
            return encdec_decode_step(params, cache, tokens, pos, cfg)
    else:
        from repro.models.lm import lm_decode_step

        def fn(params, cache, tokens, pos):
            return lm_decode_step(params, cache, tokens, pos, cfg)
    return fn, (p_struct, c_struct, tok, pos), \
        (p_specs, c_specs, tok_spec, pos_spec)
