"""QKFormer (Zhou et al., NeurIPS 2024, arXiv:2403.16552) in JAX: a
hierarchical spiking transformer trained by BPTT on the vision path.

Model = per stage i, a spiking patch embedding with deformed shortcut
(SPEDS) and ``depths[i]`` blocks at width ``widths[i]``; then the mean over
T and tokens and a linear head. The published HST-10-768 has three stages:
56x56 tokens at 192 with 1 block, 28x28 at 384 with 2, 14x14 at 768 with 7.

* Blocks use Spikformer's residual: X <- X + Attn(X), X <- X + MLP(X), each
  branch ending in an SN, so the residual stream holds spike counts.
* Attention is Q-K token attention (QKTA) in every stage but the last:
  Q, K = SN(BN(X W)); per head a token mask a = SN_attn(sum over the head's
  channels of Q); out = SN(BN((a * K) W_proj)). There is no V and no N x N
  product. The last stage runs full spiking self-attention
  (``spiking_layers.ssa_apply``).
* SPEDS: 3x3 stride-1 convs, each followed by BN and, where it halves the
  side, a 3x3 stride-2 max-pool; an SN after every conv but the last, whose
  BN output is summed with a 1x1 stride-2 conv + BN shortcut before the last
  SN. The first embedding takes the image to a quarter of its side in three
  convs (c_in -> w/2 pooled, w/2 -> w pooled, w -> w; shortcut from the
  first conv's spikes); each later one halves the side in two (w -> 2w,
  2w -> 2w pooled; shortcut from its input).

Each stage's blocks are stored stacked over depth (``stages[i]["blocks"]``
leaves are [depth, ...]) and run as a Python loop, as the Spikingformer's
are. Sites ``speds.*`` and ``qkta.*`` run as jnp under every policy; the SSA
and MLP sites share the Spikingformer's dispatch (``config.policy``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.lif import LIFConfig, lif_scan
from repro.core.policy import (ExecutionPolicy, dispatch_site, plan_sites,
                               register_site_table)
from repro.core.spiking_layers import (ACT_SPECS, SMLPConfig, SSAConfig,
                                       bn_apply, cross_entropy, init_bn,
                                       init_linear, init_projections,
                                       init_smlp, linear_apply,
                                       linear_bn_lif_apply, mlp_apply,
                                       ssa_apply, unstack_depth)
from repro.models.common import BATCH, shard

register_site_table(
    "qkformer",
    tuple(f"speds.conv.{i}" for i in range(7))
    + tuple(f"speds.shortcut.{i}" for i in range(3)) + (
        "speds.lif", "qkta.qk", "qkta.lif", "qkta.mask", "qkta.proj",
        "ssa.qkv", "ssa.lif", "attn_qk", "attn_av", "ssa.proj", "mlp.a",
        "mlp.b", "mlp.lif"),
    groups=("speds.conv", "speds.shortcut"))

#: linear_bn sites whose operand is the residual stream (spike counts, not
#: {0,1}): a bit-packed arm would pack it wrongly, so they run the dense arm
#: of whatever the policy asks.
FLOAT_INPUT_SITES = ("ssa.qkv", "mlp.a")
_DENSE_ARM = {"pallas+spike_mm": "pallas", "fused_epilogue": "pallas"}


@dataclasses.dataclass(frozen=True)
class QKFormerConfig:
    """HST-10-768 defaults (ImageNet, 224x224, T=4)."""

    #: Family tag for the unified train-step factory.
    family: ClassVar[str] = "vision"
    #: Inputs are float images (the data stream reads this).
    spike_input: ClassVar[bool] = False

    widths: tuple[int, ...] = (192, 384, 768)
    depths: tuple[int, ...] = (1, 2, 7)
    n_heads: int = 8
    mlp_ratio: int = 4
    time_steps: int = 4
    image_size: int = 224
    in_channels: int = 3
    num_classes: int = 1000
    lif: LIFConfig = LIFConfig()
    #: SN_attn of QKTA's token mask and SSA's output (threshold 0.5).
    attn_lif: LIFConfig = LIFConfig(th_fire=0.5, th_lo=-0.5, th_hi=1.5)
    attn_scale: float = 0.125
    dtype: Any = jnp.float32
    #: The policy as given, with ``speds.*``/``qkta.*`` pinned to jnp and
    #: the float-input sites to their dense arm (``__post_init__``), so the
    #: plan reports what runs.
    policy: ExecutionPolicy = ExecutionPolicy()

    def __post_init__(self):
        object.__setattr__(self, "widths", tuple(self.widths))
        object.__setattr__(self, "depths", tuple(self.depths))
        if len(self.widths) != len(self.depths) or len(self.widths) < 2:
            raise ValueError("QKFormer needs one width per stage and at "
                             "least two stages")
        if self.image_size % (2 ** (len(self.widths) + 1)):
            raise ValueError(f"image_size {self.image_size} must halve "
                             f"{len(self.widths) + 1} times")
        pins = {"qkta": "jnp", "speds": "jnp"}
        for site in FLOAT_INPUT_SITES:
            impl = self.policy.resolve(site, "linear_bn")
            pins[site] = _DENSE_ARM.get(impl, impl)
        object.__setattr__(self, "policy", self.policy.with_sites(pins))

    def with_policy(self, policy: ExecutionPolicy) -> "QKFormerConfig":
        return dataclasses.replace(self, policy=policy)

    @property
    def lif_cfg(self) -> LIFConfig:
        return dataclasses.replace(self.lif, policy=self.policy)

    @property
    def attn_lif_cfg(self) -> LIFConfig:
        return dataclasses.replace(self.attn_lif, policy=self.policy)

    def sides(self) -> tuple[int, ...]:
        """Token-grid side of each stage."""
        return tuple(self.image_size // 4 // 2 ** i
                     for i in range(len(self.widths)))

    def embed_layers(self) -> tuple:
        """Per SPEDS: its 3x3 convs as (c_in, c_out, pooled), and its 1x1
        stride-2 shortcut (c_in, c_out), which reads the input of the
        last two convs."""
        w = self.widths
        out = [(((self.in_channels, w[0] // 2, True),
                 (w[0] // 2, w[0], True), (w[0], w[0], False)),
                (w[0] // 2, w[0]))]
        for a, b in zip(w, w[1:]):
            out.append((((a, b, False), (b, b, True)), (a, b)))
        return tuple(out)

    def execution_site_specs(self) -> tuple[tuple, ...]:
        """(site, op, pack_dim, spike_operand, trailing_lif) for every
        dispatch site (see ``SpikingFormerConfig.execution_site_specs``);
        pack dims are the last stage's."""
        d, n = self.widths[-1], self.sides()[-1] ** 2
        lif = lambda site: ((site, "lif", None),)  # noqa: E731
        convs = sum(len(c) for c, _ in self.embed_layers())
        return tuple(
            (f"speds.conv.{i}", "conv", None) for i in range(convs)) + tuple(
            (f"speds.shortcut.{i}", "conv", None)
            for i in range(len(self.widths))) + lif("speds.lif") + (
            ("qkta.qk", "linear_bn", d, False, True),
        ) + lif("qkta.lif") + lif("qkta.mask") + (
            ("qkta.proj", "linear_bn", d, True, True),
            ("ssa.qkv", "linear_bn", d, False, True),
        ) + lif("ssa.lif") + (
            ("attn_qk", "attn_qk", d // self.n_heads),
            ("attn_av", "attn_av", n),
            ("ssa.proj", "linear_bn", d, True, True),
            ("mlp.a", "linear_bn", d, False, True),
            ("mlp.b", "linear_bn", self.mlp_ratio * d, True, True),
        ) + lif("mlp.lif")

    def execution_plan(self, batch: int | None = None):
        """One :class:`~repro.core.policy.SiteDecision` per site. ``batch``
        is accepted for the train path's call; no site here plans by it."""
        rows = plan_sites(self.policy, self.execution_site_specs())
        # Head dim and token count are architectural (as in the
        # Spikingformer): a ragged attention pack dim is expected.
        return [dataclasses.replace(r, expected=True)
                if r.op in ("attn_qk", "attn_av") and r.note else r
                for r in rows]

    # The vision train path's model interface (``launch.train``,
    # ``launch.specs``, ``train.loop``).
    def init(self, key):
        return init_qkformer(key, self)

    def param_specs(self):
        """Every leaf replicated (FSDP may still shard large ones)."""
        structs = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return tuple(jax.tree.map(lambda a: P(*([None] * a.ndim)), t)
                     for t in structs)

    def grad_step(self, params, state, images, labels):
        return qkformer_grad_step(params, state, images, labels, self)

    def param_count(self) -> int:
        """Parameters, counted from shapes (nothing is allocated)."""
        params, _ = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return sum(a.size for a in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# SPEDS
# ---------------------------------------------------------------------------

def max_pool(x: jax.Array) -> jax.Array:
    """3x3 stride-2 max-pool with one pixel of padding, NHWC."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1),
                                 ((0, 0), (1, 1), (1, 1), (0, 0)))


def _init_conv_bn(key, k: int, c_in: int, c_out: int, dtype):
    w = jax.random.normal(key, (k, k, c_in, c_out), dtype) * (
        k * k * c_in) ** -0.5
    bn_p, bn_s = init_bn(c_out, dtype)
    return {"conv": {"w": w}, "bn": bn_p}, {"bn": bn_s}


def _conv_bn(params, state, x, cfg: QKFormerConfig, *, stride: int,
             pooled: bool, site: str, train: bool):
    """(T,B,H,W,C) -> conv (SAME, ``stride``) -> BN [-> max-pool] at
    ``site``."""
    def run():
        t, b = x.shape[:2]
        xf = shard(x.reshape(t * b, *x.shape[2:]), BATCH, None, None, None)
        y = jax.lax.conv_general_dilated(
            xf, params["conv"]["w"].astype(xf.dtype), (stride, stride),
            "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y, bn_s = bn_apply(params["bn"], state["bn"], y, train=train,
                           policy=cfg.policy, site=site)
        if pooled:
            y = max_pool(y)
        return y.reshape(t, b, *y.shape[1:]), {"bn": bn_s}

    return dispatch_site(site, "conv", cfg.policy.resolve(site, "conv"), run)


def speds_apply(params, state, x, cfg: QKFormerConfig, stage: int, *,
                train: bool):
    """Embedding ``stage``: (T,B,H,W,C) -> spikes (T,B,H',W',C')."""
    convs, _ = cfg.embed_layers()[stage]
    first = sum(len(c) for c, _ in cfg.embed_layers()[:stage])
    new = {"conv": []}
    for j, (_, _, pooled) in enumerate(convs):
        if j == len(convs) - 2:
            skip = x
        y, s = _conv_bn(params["conv"][j], state["conv"][j], x, cfg,
                        stride=1, pooled=pooled,
                        site=f"speds.conv.{first + j}", train=train)
        new["conv"].append(s)
        if j < len(convs) - 1:
            x = lif_scan(y, cfg.lif_cfg, site="speds.lif")
    r, new["shortcut"] = _conv_bn(params["shortcut"], state["shortcut"],
                                  skip, cfg, stride=2, pooled=False,
                                  site=f"speds.shortcut.{stage}",
                                  train=train)
    return lif_scan(y + r, cfg.lif_cfg, site="speds.lif"), new


# ---------------------------------------------------------------------------
# Q-K token attention
# ---------------------------------------------------------------------------

def qkta_mask(q: jax.Array, k: jax.Array, n_heads: int,
              attn_lif: LIFConfig) -> jax.Array:
    """a = SN_attn(sum over each head's channels of Q), one value per token
    and head, broadcast over the head's channels of K: a * K, (T,B,N,D)."""
    t, b, n, d = q.shape
    a = lif_scan(q.reshape(t, b, n, n_heads, d // n_heads).sum(-1),
                 attn_lif, site="qkta.mask")
    return (a[..., None] * k.reshape(t, b, n, n_heads, d // n_heads)
            ).reshape(t, b, n, d)


def qkta_apply(params, state, x, cfg: QKFormerConfig, *, train: bool):
    """x: (T,B,N,D) residual stream -> spikes (T,B,N,D). Sites
    ``qkta.qk``, ``qkta.mask``, ``qkta.proj``; SNs at ``qkta.lif``."""
    pol, lif = cfg.policy, cfg.lif_cfg
    qk, new = [], {}
    for name in ("q", "k"):
        s, new[name] = linear_bn_lif_apply(
            params[name], state[name], x, lif, train=train, policy=pol,
            site="qkta.qk", lif_site="qkta.lif")
        qk.append(s)
    xk = dispatch_site("qkta.mask", "lif", pol.resolve("qkta.mask", "lif"),
                       lambda: qkta_mask(*qk, cfg.n_heads, cfg.attn_lif_cfg))
    y, new["proj"] = linear_bn_lif_apply(
        params["proj"], state["proj"], xk, lif, train=train, policy=pol,
        site="qkta.proj", lif_site="qkta.lif")
    return y, new


# ---------------------------------------------------------------------------
# Blocks and the whole model
# ---------------------------------------------------------------------------

def _is_qkta(cfg: QKFormerConfig, stage: int) -> bool:
    return stage < len(cfg.widths) - 1


def init_block(key, cfg: QKFormerConfig, stage: int):
    w = cfg.widths[stage]
    k_attn, k_mlp = jax.random.split(key)
    names = ("q", "k", "proj") if _is_qkta(cfg, stage) else (
        "q", "k", "v", "proj")
    p_attn, s_attn = init_projections(k_attn, w, names, cfg.dtype)
    p_mlp, s_mlp = init_smlp(k_mlp, SMLPConfig(w, cfg.mlp_ratio * w),
                             cfg.dtype)
    return {"attn": p_attn, "mlp": p_mlp}, {"attn": s_attn, "mlp": s_mlp}


def block_apply(params, state, x, cfg: QKFormerConfig, stage: int, *,
                train: bool):
    w = cfg.widths[stage]
    if _is_qkta(cfg, stage):
        a, s_attn = qkta_apply(params["attn"], state["attn"], x, cfg,
                               train=train)
    else:
        a, s_attn = ssa_apply(params["attn"], state["attn"], x, SSAConfig(
            w, cfg.n_heads, cfg.lif_cfg, cfg.attn_lif_cfg, cfg.attn_scale,
            cfg.policy), train=train)
    x = shard(x + a, *ACT_SPECS["block.residual"])
    m, s_mlp = mlp_apply(params["mlp"], state["mlp"], x, SMLPConfig(
        w, cfg.mlp_ratio * w, cfg.lif_cfg, policy=cfg.policy), train=train)
    x = shard(x + m, *ACT_SPECS["block.residual"])
    return x, {"attn": s_attn, "mlp": s_mlp}


def init_qkformer(key, cfg: QKFormerConfig):
    """(params, state): ``embed`` (one SPEDS per stage), ``stages`` (each
    ``{"blocks": ...}`` stacked over depth) and ``head``; ``state`` holds
    the BN running statistics in the same layout."""
    k_embed, k_stages, k_head = jax.random.split(key, 3)
    embed_p, embed_s = [], []
    for layers, k in zip(cfg.embed_layers(),
                         jax.random.split(k_embed, len(cfg.widths))):
        convs, (sc_in, sc_out) = layers
        keys = jax.random.split(k, len(convs) + 1)
        pairs = [_init_conv_bn(kc, 3, c_in, c_out, cfg.dtype)
                 for kc, (c_in, c_out, _) in zip(keys, convs)]
        sc_p, sc_s = _init_conv_bn(keys[-1], 1, sc_in, sc_out, cfg.dtype)
        embed_p.append({"conv": [p for p, _ in pairs], "shortcut": sc_p})
        embed_s.append({"conv": [s for _, s in pairs], "shortcut": sc_s})
    stages_p, stages_s = [], []
    for stage, k in enumerate(jax.random.split(k_stages, len(cfg.widths))):
        keys = jax.random.split(k, cfg.depths[stage])
        p, s = jax.vmap(lambda kb: init_block(kb, cfg, stage))(keys)
        stages_p.append({"blocks": p})
        stages_s.append({"blocks": s})
    head = init_linear(k_head, cfg.widths[-1], cfg.num_classes, cfg.dtype)
    head["b"] = jnp.zeros((cfg.num_classes,), cfg.dtype)
    return ({"embed": embed_p, "stages": stages_p, "head": head},
            {"embed": embed_s, "stages": stages_s})


def qkformer_apply(params, state, images, cfg: QKFormerConfig, *,
                   train: bool):
    """images: (T,B,H,W,C) or (B,H,W,C) (static image, repeated over T).
    Returns (logits (B, num_classes), new_state).

    The ops fall under the named scopes ``embed`` (each SPEDS),
    ``qkta_blocks`` (the blocks of every stage but the last), ``ssa_blocks``
    (the last stage's) and ``head``, and each site's own scope inside."""
    x = images
    if x.ndim == 4:
        x = jnp.broadcast_to(x[None], (cfg.time_steps,) + x.shape)
    new_embed, new_stages = [], []
    for stage in range(len(cfg.widths)):
        with jax.named_scope("embed"):
            x = shard(x, None, BATCH, None, None, None)
            x, s_emb = speds_apply(params["embed"][stage],
                                   state["embed"][stage], x, cfg, stage,
                                   train=train)
            new_embed.append(s_emb)
            t, b, h, w, c = x.shape
            x = shard(x.reshape(t, b, h * w, c), *ACT_SPECS["block.residual"])
        scope = "qkta_blocks" if _is_qkta(cfg, stage) else "ssa_blocks"
        with jax.named_scope(scope):
            blocks = []
            for p, s in zip(unstack_depth(params["stages"][stage]["blocks"]),
                            unstack_depth(state["stages"][stage]["blocks"])):
                x, s_new = block_apply(p, s, x, cfg, stage, train=train)
                blocks.append(s_new)
            new_stages.append({"blocks": jax.tree.map(
                lambda *xs: jnp.stack(xs), *blocks)})
        if stage < len(cfg.widths) - 1:
            x = x.reshape(t, b, h, w, c)
    with jax.named_scope("head"):
        feat = shard(jnp.mean(x, axis=(0, 2)), BATCH, None)      # (B, D)
        logits = linear_apply(params["head"], feat) + params["head"]["b"]
    return logits.astype(jnp.float32), {"embed": new_embed,
                                        "stages": new_stages}


def qkformer_loss(params, state, images, labels, cfg: QKFormerConfig):
    """BPTT training loss (traced inside the jitted train step)."""
    logits, new_state = qkformer_apply(params, state, images, cfg,
                                       train=True)
    with jax.named_scope("head"):
        loss = cross_entropy(logits, labels)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, (new_state, {"loss": loss, "accuracy": acc})


def qkformer_grad_step(params, state, images, labels, cfg: QKFormerConfig):
    """One BPTT step: returns (grads, new_state, metrics)."""
    (_, (new_state, metrics)), grads = jax.value_and_grad(
        qkformer_loss, has_aux=True)(params, state, images, labels, cfg)
    return grads, new_state, metrics
