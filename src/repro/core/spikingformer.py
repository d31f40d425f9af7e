"""Spikingformer (the paper's representative Spiking Transformer) in JAX.

Model = Spiking Tokenizer (conv downsampling + spike encoding, eq. 4)
      + L Spiking Transformer Blocks (PSSA + SMLP, eq. 5-6)
      + GAP + FC classification head (eq. 7).

Training is BPTT (paper §II-C): the time axis is scanned (``lax.scan``) and
autodiff through the LIF surrogate reproduces eq. 12. Blocks are homogeneous:
their parameters and BN state are stored stacked over depth ([L, ...]
leaves), but they run as a Python loop over the L blocks, not a scan. Under
BPTT a depth scan copies every residual the backward needs into an
[L, ...] buffer and slices it out again; in the loop each residual stays
where its block wrote it.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.lif import LIFConfig, lif_scan
from repro.core.policy import (ExecutionPolicy, FUSED_EPILOGUE_IMPLS,
                               apply_legacy_exec_flags,
                               fused_epilogue_fallback, get_kernel,
                               plan_sites, policy_from_flags,
                               register_kernel, register_site_table,
                               runtime_fallback, warn_deprecated_flags)
from repro.core.spiking_layers import (ACT_SPECS, BlockConfig, _bn_pallas,
                                       _neuron_layer_site, bn_apply,
                                       block_apply, init_block, init_bn,
                                       init_linear, linear_apply)
from repro.models.common import BATCH, MODEL, shard, spec_is_leaf

Params = dict[str, Any]
State = dict[str, Any]

#: Site table for construction-time ExecutionPolicy validation: every site
#: this model dispatches through (per-stage conv sites at the paper's
#: 224/14 geometry, 4 stages). The "tokenizer.conv" group admits any stage
#: index, so shallower/deeper tokenizers stay addressable as a group.
register_site_table(
    "spikingformer",
    tuple(f"tokenizer.conv.{i}" for i in range(4)) + (
        "tokenizer.bn", "tokenizer.lif", "pssa.lif", "pssa.qkv",
        "attn_qk", "attn_av", "pssa.proj", "smlp.lif", "smlp.a", "smlp.b"),
    groups=("tokenizer.conv",))


@dataclasses.dataclass(frozen=True)
class SpikingFormerConfig:
    """Paper Table III defaults: h=8, d=512, T=4, P=14, BS=16."""

    #: Family tag for the unified train-step factory (the LM/audio configs
    #: carry "lm"/"audio" in the same slot).
    family: ClassVar[str] = "vision"

    num_layers: int = 8
    d_model: int = 512
    n_heads: int = 8
    d_ff: int = 2048                  # MLP ratio 4
    time_steps: int = 4
    image_size: int = 224
    in_channels: int = 3
    patch_grid: int = 14              # P: final N = P*P tokens
    num_classes: int = 1000
    lif: LIFConfig = LIFConfig()
    qk_first: bool = True             # paper-faithful (QK^T)V order
    attn_scale: float = 0.125
    dtype: Any = jnp.float32
    remat: bool = False               # checkpoint each block
    # Temporal tiling (the paper's temporal blocking): every LIF scan splits
    # its T axis into remat'd chunks of this length with the (U, S) carry
    # threaded across chunk boundaries — stored BPTT residuals scale with
    # T/time_chunk instead of T, gradients stay exact. None = single-shot.
    time_chunk: int | None = None
    # True when the input frames are pre-encoded {0,1} spikes (DVS-style
    # event data): the *first* tokenizer stage then also qualifies for the
    # bit-packed spike-conv path (stages >= 2 always consume LIF spikes).
    spike_input: bool = False
    # Execution policy for every LIF/BN/matmul/attention site; derived
    # configs (Block/PSSA/SMLP/LIF) inherit it. See docs/EXECUTION.md.
    policy: ExecutionPolicy = ExecutionPolicy()
    # Deprecated PR 1 spellings, folded into ``policy`` with a warning:
    backend: dataclasses.InitVar[str | None] = None
    spike_mm: dataclasses.InitVar[bool | None] = None
    interpret: dataclasses.InitVar[bool | None] = None

    def __post_init__(self, backend, spike_mm, interpret):
        apply_legacy_exec_flags(self, backend, spike_mm, interpret)

    @property
    def block(self) -> BlockConfig:
        return BlockConfig(self.d_model, self.n_heads, self.d_ff,
                           self.lif_cfg, self.qk_first, self.attn_scale,
                           policy=self.policy)

    @property
    def lif_cfg(self) -> LIFConfig:
        """LIF config with the model policy + temporal tiling injected."""
        return dataclasses.replace(self.lif, policy=self.policy,
                                   time_chunk=self.time_chunk)

    def with_policy(self, policy: ExecutionPolicy) -> "SpikingFormerConfig":
        """Same model, different execution policy (params are compatible)."""
        return dataclasses.replace(self, policy=policy)

    def with_backend(self, backend: str, *, spike_mm: bool | None = None,
                     interpret: bool | None = None) -> "SpikingFormerConfig":
        """Deprecated: use ``with_policy(ExecutionPolicy(...))``."""
        warn_deprecated_flags("SpikingFormerConfig.with_backend()")
        return self.with_policy(policy_from_flags(backend, spike_mm,
                                                  interpret,
                                                  base=self.policy))

    @property
    def num_tokens(self) -> int:
        return self.patch_grid * self.patch_grid

    @property
    def tokenizer_stages(self) -> int:
        n = self.image_size // self.patch_grid
        stages = max(1, n.bit_length() - 1)   # log2 downsample factor
        assert self.patch_grid * (2 ** stages) == self.image_size, (
            "image_size must be patch_grid * 2^k")
        return stages

    def tokenizer_stage_channels(self) -> tuple[tuple[int, int], ...]:
        """(c_in, c_out) for each eq. 4 tokenizer stage, in order."""
        stages = self.tokenizer_stages
        chans, c_in = [], self.in_channels
        for i in range(stages):
            c_out = self.d_model // (2 ** (stages - 1 - i))
            chans.append((c_in, c_out))
            c_in = c_out
        return tuple(chans)

    def execution_site_specs(self) -> tuple[tuple, ...]:
        """(site, op, pack_dim[, spike_operand]) for every dispatch site in
        this model — the input to :func:`repro.core.policy.plan_sites`.
        ``pack_dim`` is the contraction dimension a bit-packed
        implementation would pack; ``spike_operand`` says whether that
        operand is {0,1}-valued at the site.

        The tokenizer convs are per-stage sites (``tokenizer.conv.<i>``, a
        group override ``"tokenizer.conv"`` covers them all): each stage
        packs its im2col contraction ``k*k*c_in`` and only stages fed by
        spikes (stage >= 2, plus stage 1 under ``spike_input``) qualify for
        the packed arm — the first float-image stage demotes to the dense
        im2col arm of the same fused pipeline as an *expected* decision.

        The attn sites only exist under ``qk_first=True``; the reassociated
        Q(K^T V) path is a dense-product einsum pair that never dispatches
        through the registry, so listing them would make the reported plan
        claim an attention impl that never runs.
        """
        head_dim = self.d_model // self.n_heads
        attn = (
            ("attn_qk", "attn_qk", head_dim),
            ("attn_av", "attn_av", self.num_tokens),
        ) if self.qk_first else ()
        # Under temporal tiling the LIF sites dispatch the state-carrying
        # twin op, so the plan lists (and validates) those rows too.
        lif_ops = ("lif", "lif_state") if self.time_chunk else ("lif",)
        lif = lambda site: tuple((site, op, None) for op in lif_ops)  # noqa
        conv = tuple(
            (f"tokenizer.conv.{i}", "conv", 9 * c_in,
             self.spike_input if i == 0 else True)
            for i, (c_in, _) in enumerate(self.tokenizer_stage_channels()))
        # 5th spec element: whether a trailing SN follows the matmul at the
        # site (a fused-epilogue impl can only serve those). Q/K/V and
        # SMLP-A feed an SN; the Z projection and SMLP-B feed residual adds.
        return conv + (
            ("tokenizer.bn", "bn", None),
        ) + lif("tokenizer.lif") + lif("pssa.lif") + (
            ("pssa.qkv", "linear_bn", self.d_model, True, True),
        ) + attn + (
            ("pssa.proj", "linear_bn", self.d_model, True, False),
        ) + lif("smlp.lif") + (
            ("smlp.a", "linear_bn", self.d_model, True, True),
            ("smlp.b", "linear_bn", self.d_ff, True, False),
        )

    def execution_plan(self, batch: int | None = None):
        """Resolve the policy once against this model's shapes: one
        :class:`~repro.core.policy.SiteDecision` per site, with packing
        fallbacks decided here rather than silently per call.

        Given the global ``batch`` of a train step, fused-epilogue sites
        whose train-arm megakernel cannot hold the site's rows in VMEM on
        the compiling backend are planned onto their pipeline arm (the
        same decision the per-call guard makes, through
        :func:`repro.core.spiking_layers.train_arm_vmem_demotion`); eval
        keeps the single launch, which tiles M.

        Stages running a fused conv impl fold their BN into the
        Conv->BN->LIF pipeline (RTFormer-style re-parameterization in
        eval, the fused BN kernel in train), so the ``tokenizer.bn`` row
        is annotated: "never dispatched" when every stage is fused,
        otherwise naming how many stages still dispatch it. Stages running
        the single-launch ``fused_epilogue`` megakernel additionally absorb
        the SOMA epilogue, so the ``tokenizer.lif`` row is annotated the
        same way.
        """
        rows = plan_sites(self.policy, self.execution_site_specs())
        # Attention pack dims are architectural: head_dim = d_model/n_heads
        # and N = patch_grid^2 are fixed by the hyperparameters, so a ragged
        # dim there (e.g. N=196 at the paper geometry) is a property of the
        # model, not a policy mistake — the demotion is expected, unlike a
        # ragged conv/linear contraction, which a channel-count change fixes.
        rows[:] = [dataclasses.replace(r, expected=True)
                   if r.op in ("attn_qk", "attn_av") and r.note else r
                   for r in rows]
        if batch is not None:
            rows[:] = [self._plan_train_capacity(r, batch) for r in rows]
        conv_rows = [r for r in rows if r.op == "conv"]

        def annotate(site, subset, what):
            if not subset:
                return
            if len(subset) == len(conv_rows):
                note = f"{what} (never dispatched)"
            else:
                note = (f"{what} at {len(subset)}/{len(conv_rows)} stages "
                        f"(still dispatches at the others)")
            rows[:] = [dataclasses.replace(r, note=note, expected=True)
                       if r.site == site else r for r in rows]

        annotate("tokenizer.bn",
                 [r for r in conv_rows if r.effective in FUSED_CONV_IMPLS],
                 "folded into the fused conv stages")
        annotate("tokenizer.lif",
                 [r for r in conv_rows
                  if r.effective in SINGLE_LAUNCH_CONV_IMPLS],
                 "absorbed into the single-launch neuron-layer megakernel")
        return rows

    def _plan_train_capacity(self, row, batch: int):
        """``row``, moved to its pipeline arm if the train-mode megakernel
        cannot hold the site at ``batch`` (see :meth:`execution_plan`)."""
        from repro.core.spiking_layers import train_arm_vmem_demotion

        if row.effective not in FUSED_EPILOGUE_IMPLS:
            return row
        reason = train_arm_vmem_demotion(
            *fused_site_geometries(self, batch)[row.site], row.packed,
            self.policy)
        if reason is None:
            return row
        pipeline = (fused_epilogue_fallback(row.op, row.effective)
                    if row.op == "linear_bn"
                    else "pallas_packed" if row.packed else "pallas")
        note = (f"{reason} at batch {batch} -> {pipeline} in train "
                f"(eval keeps the single launch)")
        return dataclasses.replace(
            row, effective=pipeline, expected=True,
            note="; ".join(n for n in (row.note, note) if n))

    def describe_execution(self, mesh=None, batch: int | None = None) -> str:
        """The per-site dispatch table (printed by bench_model_table),
        followed by the active tuned-block table's entries for this model's
        sites (``repro.tune`` — which block sizes/arms kernel dispatch will
        pick up at trace time), then the sharding plan: the activation
        partition specs the model constrains to, and — when ``mesh`` is
        given — the effective parameter shardings (post sanitize + FSDP)
        on that mesh. ``batch`` plans the train step's capacity demotions
        (see :meth:`execution_plan`)."""
        from repro.core.policy import describe_breaker
        from repro.tune.table import describe_tuned

        rows = self.execution_plan(batch)
        out = self.policy.describe(rows=rows)
        tuned = describe_tuned([r.site for r in rows])
        breaker = describe_breaker()
        if breaker:
            out = out + "\n\n" + breaker
        return out + "\n\n" + tuned + "\n\n" + self.describe_sharding(mesh)

    def describe_sharding(self, mesh=None) -> str:
        """The sharding half of the execution report (see docs/SHARDING.md).

        Batch shards over the ("pod", "data") mesh axes, d_model/head
        projections over "model". Without a mesh the table shows the logical
        specs; with one, the per-leaf parameter placements actually used by
        ``launch.train.build_spikingformer_state`` on that mesh.
        """
        lines = ["# Sharding plan (batch over ('pod','data'), "
                 "tensor-parallel over 'model')", "activation,spec"]
        for name, spec in activation_specs(self):
            lines.append(f"{name},{spec}")
        if mesh is not None:
            from repro.launch.specs import spikingformer_structs
            _, (specs, _) = spikingformer_structs(self, mesh)
            sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
            lines.append(f"param,spec  (mesh {sizes})")
            flat = jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=spec_is_leaf)[0]
            for path, spec in flat:
                name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                                for p in path)
                lines.append(f"{name},{spec}")
        return "\n".join(lines)

    def param_count(self) -> int:
        d, f = self.d_model, self.d_ff
        per_block = 4 * d * d + 2 * d * f + 10 * d + 2 * f
        tok = sum(9 * ci * co + 2 * co
                  for ci, co in self.tokenizer_stage_channels())
        head = self.d_model * self.num_classes + self.num_classes
        return self.num_layers * per_block + tok + head


def fused_site_geometries(cfg: SpikingFormerConfig, batch: int
                          ) -> dict[str, tuple]:
    """``site -> (t, m, c, k)`` matmul geometry for every fused-epilogue
    candidate site of a Spikingformer config, at global batch ``batch`` —
    the inputs :func:`repro.kernels.neuron_layer.train_arm_vmem_bytes`
    prices. Conv stages use their im2col geometry (rows = batch x out-pixel
    count, contraction = 9 x c_in); the Q/K/V projections share one site
    and one geometry."""
    t, n, d = cfg.time_steps, cfg.num_tokens, cfg.d_model
    geoms: dict[str, tuple] = {}
    h = cfg.image_size
    for i, (c_in, c_out) in enumerate(cfg.tokenizer_stage_channels()):
        h //= 2
        geoms[f"tokenizer.conv.{i}"] = (t, batch * h * h, 9 * c_in, c_out)
    geoms["pssa.qkv"] = (t, batch * n, d, d)
    geoms["pssa.proj"] = (t, batch * n, d, d)
    geoms["smlp.a"] = (t, batch * n, d, cfg.d_ff)
    geoms["smlp.b"] = (t, batch * n, cfg.d_ff, d)
    return geoms


# ---------------------------------------------------------------------------
# Sharding plan: logical partition specs for params and activations
# ---------------------------------------------------------------------------

def activation_specs(cfg: SpikingFormerConfig
                     ) -> tuple[tuple[str, P], ...]:
    """(name, PartitionSpec) for every activation constraint the model
    places (the same specs ``shard(...)`` is called with, so this table IS
    the plan, not a parallel description of it). Activations are (T, B, N,
    D) unless noted; batch shards over ("pod", "data"), the Q/K/V, head and
    MLP-hidden projections over "model"; the residual stream keeps features
    replicated (its D is the sum of row-parallel outputs)."""
    return (
        ("images", P(None, BATCH, None, None, None)),     # (T,B,H,W,C)
        ("tokenizer.stage", P(None, BATCH, None, None, None)),  # (T,B,H,W,C)
        ("tokenizer.stage.folded", P(BATCH, None, None, None)),  # (T*B,H,W,C)
        ("tokenizer.patches", P(None, BATCH, None)),      # im2col (T,M,kkC)
        ("tokenizer.tokens", P(None, BATCH, None, None)),
        ("block.residual", ACT_SPECS["block.residual"]),
        ("pssa.qkv", ACT_SPECS["pssa.qkv"]),
        ("attn.scores", ACT_SPECS["attn.scores"]),        # (T,B,h,N,M)
        ("pssa.out", ACT_SPECS["pssa.out"]),
        ("smlp.hidden", ACT_SPECS["smlp.hidden"]),
        ("head.features", P(BATCH, None)),                # (B, D)
    )


def spikingformer_param_specs(cfg: SpikingFormerConfig):
    """(param_specs, state_specs) PartitionSpec pytrees matching
    :func:`init_spikingformer`.

    Tensor-parallel placements mirror the Megatron convention: Q/K/V and
    SMLP-A column-parallel (output features over "model", with their BN
    leaves sharded alike), Z-projection and SMLP-B row-parallel (input
    features over "model", BN replicated). The vmapped block leaves carry a
    leading L depth axis that stays unsharded (``spikingformer_scan_dims``
    tells ``apply_fsdp`` to skip it). Tokenizer convs and the head are
    replicated — FSDP may still shard them over "data"."""
    rep = P(None)
    tok_p = [{"conv": {"w": P(None, None, None, None)},
              "bn": {"gamma": rep, "beta": rep}}
             for _ in range(cfg.tokenizer_stages)]
    tok_s = [{"bn": {"mean": rep, "var": rep}} for _ in
             range(cfg.tokenizer_stages)]

    def linear_bn(w_spec, feat_spec):
        return ({"linear": {"w": w_spec},
                 "bn": {"gamma": feat_spec, "beta": feat_spec}},
                {"bn": {"mean": feat_spec, "var": feat_spec}})

    col_p, col_s = linear_bn(P(None, None, MODEL), P(None, MODEL))
    row_p, row_s = linear_bn(P(None, MODEL, None), P(None, None))
    blocks_p = {"pssa": {"q": col_p, "k": col_p, "v": col_p, "z": row_p},
                "smlp": {"a": col_p, "b": row_p}}
    blocks_s = {"pssa": {"q": col_s, "k": col_s, "v": col_s, "z": row_s},
                "smlp": {"a": col_s, "b": row_s}}
    head = {"w": P(None, None), "b": P(None)}
    return ({"tokenizer": tok_p, "blocks": blocks_p, "head": head},
            {"tokenizer": tok_s, "blocks": blocks_s})


def lif_residual_accounting(cfg: SpikingFormerConfig, batch: int
                            ) -> dict[str, int]:
    """Analytic stored-residual accounting for the LIF sites of one BPTT
    step (fp32 bytes; the time-chunk memory math of docs/SHARDING.md).

    ``single_shot``: the SOMA path persists (U, S, mask) for all T steps of
    every LIF site between FP and BP — 3·T·rows elements. ``tiled`` (with
    ``time_chunk`` set): the remat'd chunk scan stores only the (U, S)
    carries at the T/time_chunk chunk boundaries plus one transient chunk
    of (U, S, mask) recomputed during BP — 2·(T/tc)·rows + 3·tc·rows.
    ``rows`` is the per-time-step element count summed over all LIF sites.
    """
    t = cfg.time_steps
    rows = 0
    h = w = cfg.image_size
    for _, c_out in cfg.tokenizer_stage_channels():
        h, w = h // 2, w // 2
        rows += batch * h * w * c_out
    # per layer: PSSA scans x, q, k, v, out (5 d-wide) + SMLP scans x
    # (d-wide) and the hidden (d_ff-wide)
    rows += cfg.num_layers * batch * cfg.num_tokens * \
        (6 * cfg.d_model + cfg.d_ff)
    single = 3 * t * rows * 4
    tc = cfg.time_chunk or t
    if not (0 < tc < t) or t % tc != 0:
        tiled = single                     # degenerate: single-shot scan
    else:
        tiled = (2 * (t // tc) + 3 * tc) * rows * 4
    return {"elems_per_step": rows, "single_shot_bytes": single,
            "tiled_bytes": tiled}


def spikingformer_scan_dims(specs):
    """Per-leaf count of leading stacked dims ``apply_fsdp`` must not
    shard: 1 for the block leaves stacked over depth, 0 elsewhere."""
    def n_scan(path, _):
        return 1 if any(getattr(p, "key", None) == "blocks" for p in path) \
            else 0
    return jax.tree_util.tree_map_with_path(
        n_scan, specs, is_leaf=spec_is_leaf)


# ---------------------------------------------------------------------------
# Spiking Tokenizer: [Conv(k3,s2) -> BN -> LIF] x stages  (eq. 4)
#
# The ``conv`` registry op is one *full* eq. 4 stage on a time-major
# (T, B, H, W, C) input, returning (spikes, new_state). Implementations:
#
# * ``"jnp"``           — the reference pipeline: dense XLA conv, then the
#                         BN and LIF dispatched through their own sites
#                         (``tokenizer.bn`` / ``tokenizer.lif``), i.e. three
#                         kernels and two HBM-materialized intermediates.
# * ``"pallas"``        — the fused conv_bn_lif pipeline, dense-im2col arm:
#                         the conv lowers to one time-major matmul
#                         (contraction k*k*c_in), BN is folded into the
#                         weights/bias (eval) or handled by the fused BN
#                         kernel in the same pass (train), and the matmul
#                         output feeds the fused SOMA epilogue directly in
#                         its (T, M, K) layout — ``tokenizer.bn`` never
#                         dispatches as a separate kernel.
# * ``"pallas_packed"`` — same pipeline with the im2col patches bit-packed
#                         to 1 bit/element through the batched spike-matmul
#                         kernel (spike inputs only; k*k*c_in % 8 == 0).
# * ``"fused_epilogue"`` — the whole stage as ONE Pallas launch: the im2col
#                         matmul (bit-packed on spike inputs), BN (batch
#                         stats in-kernel in train, RTFormer-folded in
#                         eval) and the SOMA membrane update run in a
#                         single kernel — neither ``tokenizer.bn`` nor
#                         ``tokenizer.lif`` dispatches, and the (T, M, K)
#                         pre-activation never exists in HBM.
# ---------------------------------------------------------------------------

#: conv impls that run a fused Conv->BN->LIF pipeline (BN folded in).
FUSED_CONV_IMPLS: frozenset[str] = frozenset({"pallas", "pallas_packed",
                                              "fused_epilogue"})

#: conv impls that additionally absorb the SOMA epilogue into the same
#: single kernel launch (``tokenizer.lif`` never dispatches).
SINGLE_LAUNCH_CONV_IMPLS: frozenset[str] = frozenset({"fused_epilogue"})


def _conv_init(key, c_in, c_out, dtype):
    w = jax.random.normal(key, (3, 3, c_in, c_out), dtype) * (9 * c_in) ** -0.5
    return {"w": w}


@register_kernel("conv", "jnp")
def _conv_stage_jnp(params, state, x, lif_cfg, train, spike_in, policy,
                    site):
    """Reference eq. 4 stage: dense conv -> BN -> LIF, each stage sub-op
    dispatched through the policy at its own site — the baseline the fused
    conv_bn_lif parity tests compare against."""
    t, b, h, w, c = x.shape
    xf = shard(x.reshape(t * b, h, w, c), BATCH, None, None, None)
    y = jax.lax.conv_general_dilated(
        xf, params["conv"]["w"].astype(xf.dtype), window_strides=(2, 2),
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # BN over (TB,H,W) per channel; LIF scans time, so unfold T.
    y, bn_s = bn_apply(params["bn"], state["bn"], y, train=train,
                       policy=policy, site="tokenizer.bn")
    tb, hh, wh, ch = y.shape
    spikes = lif_scan(y.reshape(t, b, hh, wh, ch), lif_cfg,
                      site="tokenizer.lif")
    return spikes, {"bn": bn_s}


def _im2col_patches(params, x):
    """Shared prologue of every fused conv arm: lower the k3/s2 stage input
    (T, B, H, W, C) to time-major im2col patches (T, M, k*k*c_in) with the
    batch sharding constraint applied, plus the (k*k*c_in, c_out) weight
    matrix and the output spatial dims."""
    from repro.kernels import conv_spike

    t, b, h, w, c = x.shape
    patches = conv_spike.im2col(x.reshape(t * b, h, w, c))
    _, ho, wo, cdim = patches.shape
    patches = shard(patches.reshape(t, b * ho * wo, cdim),
                    None, BATCH, None)                      # (T, M, k*k*c_in)
    w_mat = conv_spike.conv_w_matrix(params["conv"]["w"])
    return patches, w_mat, (t, b, ho, wo, cdim)


def conv_bn_lif_fused(params, state, x, lif_cfg, train, spike_in, policy,
                      site, *, packed):
    """Fused eq. 4 stage: im2col matmul + folded BN + fused LIF epilogue.

    The k3/s2 conv lowers to a single time-major matmul ``patches (T, M,
    k*k*c_in) @ w (k*k*c_in, c_out)``; with ``packed=True`` and a spike
    input whose contraction is a multiple of 8, the patches ride the
    bit-packed batched spike kernel (1 bit/element across HBM), otherwise
    the dense einsum arm of the same pipeline runs (logged when that
    disagrees with a packed request).

    BN never dispatches at ``tokenizer.bn``: in eval it folds into the
    matmul weights and a bias (RTFormer-style re-parameterization, exact
    for running statistics); in train the batch statistics depend on the
    conv output, so the fused BN kernel computes and applies them in its
    single VMEM visit — the same split ``linear_bn_apply`` uses. The
    matmul output is already in the (T, M, K) time-major layout the SOMA
    kernel consumes, so the LIF epilogue (dispatched at ``tokenizer.lif``,
    temporal tiling included) runs with no layout shuffle in between.
    """
    from repro.kernels import conv_spike, ops  # deferred: jnp path stays light

    patches, w_mat, (t, b, ho, wo, cdim) = _im2col_patches(params, x)
    k_out = w_mat.shape[-1]
    use_packed = packed and spike_in and cdim % 8 == 0
    if packed and not use_packed:
        reason = (f"im2col dim {cdim} % 8 != 0" if spike_in
                  else "float (non-spike) input")
        # A float first stage is a planned, structural demotion (INFO); a
        # ragged contraction is a real constraint violation (WARNING).
        runtime_fallback(site, "pallas_packed",
                         reason + " -> dense im2col arm",
                         expected=not spike_in)

    def matmul(weights):
        if use_packed:
            from repro.tune.table import lookup as tuned_lookup

            tb = tuned_lookup(site, "conv", "pallas_packed",
                              (t, patches.shape[1], cdim, k_out), True)
            return ops.spike_patch_mm_train_op(
                patches, weights.astype(patches.dtype), policy.interpret,
                tb.mm_blocks() if tb else None)
        return jnp.einsum("tmc,ck->tmk", patches,
                          weights.astype(patches.dtype))

    bn_p, bn_s = params["bn"], state["bn"]
    if train:
        # Batch statistics depend on the conv output, so the fused BN
        # kernel computes and applies them in its one VMEM visit — the
        # same _bn_pallas (and momentum/eps) the Conv1DBN sites use.
        y, new_bn = _bn_pallas(bn_p, bn_s, matmul(w_mat), True, 0.9, 1e-5,
                               policy, site)
    else:
        w_fold, bias = conv_spike.fold_bn(w_mat, bn_p["gamma"], bn_p["beta"],
                                          bn_s["mean"], bn_s["var"])
        y = matmul(w_fold) + bias.astype(patches.dtype)
        new_bn = bn_s
    spikes = lif_scan(y, lif_cfg, site="tokenizer.lif")     # (T, M, K)
    return spikes.reshape(t, b, ho, wo, k_out), {"bn": new_bn}


@register_kernel("conv", "pallas")
def _conv_stage_im2col(params, state, x, lif_cfg, train, spike_in, policy,
                       site):
    """Dense-im2col arm of the fused conv_bn_lif pipeline (also the planned
    fallback of ``pallas_packed`` on ragged or float-input stages)."""
    return conv_bn_lif_fused(params, state, x, lif_cfg, train, spike_in,
                             policy, site, packed=False)


@register_kernel("conv", "pallas_packed")
def _conv_stage_packed(params, state, x, lif_cfg, train, spike_in, policy,
                       site):
    """Bit-packed arm: im2col patches cross HBM at 1 bit/element through
    the batched spike-matmul kernel (spike inputs, k*k*c_in % 8 == 0)."""
    return conv_bn_lif_fused(params, state, x, lif_cfg, train, spike_in,
                             policy, site, packed=True)


@register_kernel("conv", "fused_epilogue")
def _conv_stage_megakernel(params, state, x, lif_cfg, train, spike_in,
                           policy, site):
    """Single-launch eq. 4 stage: ONE Pallas kernel computes the im2col
    matmul (bit-packed on spike inputs with ``k*k*c_in % 8 == 0``, dense
    arm otherwise — logged, never silent), applies BN (batch statistics
    in-kernel in train, RTFormer-folded weights in eval) and runs the SOMA
    membrane update with the (U, S) carry in VMEM. Neither ``tokenizer.bn``
    nor ``tokenizer.lif`` dispatches, and no pre-activation crosses HBM —
    3 launches -> 1 per stage.
    """
    from repro.core.spiking_layers import (_train_arm_exceeds_vmem,
                                           _tuned_prefers_pipeline)
    from repro.tune.table import lookup as tuned_lookup

    patches, w_mat, (t, b, ho, wo, cdim) = _im2col_patches(params, x)
    packed = spike_in and cdim % 8 == 0
    shape4 = (t, patches.shape[1], cdim, w_mat.shape[-1])
    if train and (_train_arm_exceeds_vmem(patches, w_mat.shape[-1], packed,
                                          policy, site)
                  or _tuned_prefers_pipeline(site, "conv", "fused_epilogue",
                                             shape4, packed, policy)):
        # Demotion on a compiling backend — VMEM capacity estimate or a
        # measured tuned-table verdict: the pipeline arm of the same fused
        # conv (M-tiled matmul + fused BN + SOMA epilogue).
        return conv_bn_lif_fused(params, state, x, lif_cfg, train, spike_in,
                                 policy, site, packed=packed)
    if not packed:
        reason = (f"im2col dim {cdim} % 8 != 0" if spike_in
                  else "float (non-spike) input")
        # The float first stage is the planned structural decision (INFO);
        # a ragged contraction is a real constraint violation (WARNING).
        runtime_fallback(site, "fused_epilogue",
                         reason + " -> dense arm (still fused)",
                         expected=not spike_in)
    tb = tuned_lookup(site, "conv", "fused_epilogue", shape4, packed)
    spikes, bn_s = _neuron_layer_site(patches, w_mat, params["bn"],
                                      state["bn"], lif_cfg, train, packed,
                                      policy.interpret, tb)
    return spikes.reshape(t, b, ho, wo, w_mat.shape[-1]), {"bn": bn_s}


def init_tokenizer(key, cfg: SpikingFormerConfig):
    keys = jax.random.split(key, cfg.tokenizer_stages)
    params, states = [], []
    for i, (c_in, c_out) in enumerate(cfg.tokenizer_stage_channels()):
        p_conv = _conv_init(keys[i], c_in, c_out, cfg.dtype)
        p_bn, s_bn = init_bn(c_out, cfg.dtype)
        params.append({"conv": p_conv, "bn": p_bn})
        states.append({"bn": s_bn})
    return params, states


def tokenizer_apply(params, state, images, cfg: SpikingFormerConfig, *,
                    train: bool):
    """images: (T, B, H, W, C) -> spike patches (T, B, N, D).

    Each stage dispatches the full-stage ``conv`` op at its own site
    (``tokenizer.conv.<i>``): the jnp reference runs Conv -> BN -> LIF as
    three dispatches, the fused impls collapse the stage into one im2col
    matmul (+ folded BN) feeding the SOMA epilogue. Stage 1 sees spikes
    only under ``cfg.spike_input``; later stages always do (LIF outputs).
    """
    pol = cfg.policy
    x, spike_in = images, cfg.spike_input
    new_states = []
    for i, (p, s) in enumerate(zip(params, state)):
        site = f"tokenizer.conv.{i}"
        from repro.core.policy import dispatch_kernel
        x = shard(x, None, BATCH, None, None, None)
        x, s_new = dispatch_kernel(site, "conv", pol.resolve(site, "conv"),
                                   p, s, x, cfg.lif_cfg, train, spike_in,
                                   pol, site)
        new_states.append(s_new)
        spike_in = True                        # LIF output feeds stage i+1
    t, b = x.shape[:2]
    return x.reshape(t, b, -1, x.shape[-1]), new_states


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def init_spikingformer(key, cfg: SpikingFormerConfig):
    k_tok, k_blocks, k_head = jax.random.split(key, 3)
    p_tok, s_tok = init_tokenizer(k_tok, cfg)
    block_keys = jax.random.split(k_blocks, cfg.num_layers)
    p_blocks, s_blocks = jax.vmap(
        lambda k: init_block(k, cfg.block, cfg.dtype))(block_keys)
    p_head = init_linear(k_head, cfg.d_model, cfg.num_classes, cfg.dtype)
    p_head["b"] = jnp.zeros((cfg.num_classes,), cfg.dtype)
    params = {"tokenizer": p_tok, "blocks": p_blocks, "head": p_head}
    state = {"tokenizer": s_tok, "blocks": s_blocks}
    return params, state


def _unstack(tree) -> list:
    """Split every [L, ...] leaf of a stacked block tree once (one
    ``lax.split``, whose transpose is one concatenate) into L per-block
    trees."""
    leaves, treedef = jax.tree.flatten(tree)
    n = leaves[0].shape[0]
    parts = [[jnp.squeeze(c, 0) for c in jnp.split(leaf, n)]
             for leaf in leaves]
    return [treedef.unflatten([p[i] for p in parts]) for i in range(n)]


def spikingformer_apply(params: Params, state: State, images: jax.Array,
                        cfg: SpikingFormerConfig, *, train: bool):
    """images: (T,B,H,W,C) or (B,H,W,C) (static image, repeated over T).

    Returns (logits (B, num_classes), new_state).

    The ops fall under the named scopes ``tokenizer``, ``blocks`` and
    ``head`` (and each site's own scope inside them), so a profile of the
    compiled step attributes device time to them.
    """
    with jax.named_scope("tokenizer"):
        if images.ndim == 4:  # static dataset: replicate over time
            images = jnp.broadcast_to(images[None],
                                      (cfg.time_steps,) + images.shape)
        images = shard(images, None, BATCH, None, None, None)
        x, s_tok = tokenizer_apply(params["tokenizer"], state["tokenizer"],
                                   images, cfg, train=train)
        x = shard(x, None, BATCH, None, None)

    def layer(p, s, x):
        return block_apply(p, s, x, cfg.block, train=train)

    if cfg.remat:
        layer = jax.checkpoint(layer)
    with jax.named_scope("blocks"):
        new_states = []
        for p, s in zip(_unstack(params["blocks"]),
                        _unstack(state["blocks"])):
            x, s_new = layer(p, s, x)
            new_states.append(s_new)
        s_blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *new_states)
    with jax.named_scope("head"):
        # eq. 7: GAP over tokens, rate-decode over time, then FC.
        feat = shard(jnp.mean(x, axis=(0, 2)), BATCH, None)   # (B, D)
        logits = linear_apply(params["head"], feat) + params["head"]["b"]
    return logits.astype(jnp.float32), {"tokenizer": s_tok, "blocks": s_blocks}


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def spikingformer_loss(params, state, images, labels, cfg: SpikingFormerConfig):
    """BPTT training loss. Deliberately NOT jitted: it is traced inside the
    already-jitted train step (a nested jit would re-trace there for
    nothing). Direct callers wanting a compiled entry point should use
    :func:`spikingformer_loss_jit`."""
    logits, new_state = spikingformer_apply(params, state, images, cfg,
                                            train=True)
    with jax.named_scope("head"):
        loss = cross_entropy(logits, labels)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, (new_state, {"loss": loss, "accuracy": acc})


#: Compiled entry point for direct callers (the train step builds its own
#: jit around :func:`spikingformer_grad_step` instead).
spikingformer_loss_jit = partial(jax.jit, static_argnames=("cfg",))(
    spikingformer_loss)


def spikingformer_grad_step(params, state, images, labels,
                            cfg: SpikingFormerConfig):
    """One BPTT step: returns (grads, new_state, metrics)."""
    (loss, (new_state, metrics)), grads = jax.value_and_grad(
        spikingformer_loss, has_aux=True)(params, state, images, labels, cfg)
    return grads, new_state, metrics
