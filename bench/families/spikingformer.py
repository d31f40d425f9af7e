"""The Spikingformer family: what the harness needs for a configuration
that names ``"family": "spikingformer"``.

* :func:`program` -- the program's own train step, built as
  ``repro.launch.train.train_vision`` builds it: state from
  ``build_spikingformer_state``, the pure step of
  ``repro.train.loop.make_train_step``, and the execution plan (site ->
  implementation that runs it) at the cell's batch;
* :func:`traffic` -- image batches from ``bench/traffic.py``, as the step
  takes them (images, labels); :data:`THROUGHPUT` counts images;
* :func:`init` -- the initialisation the configuration's ``init`` states,
  by leaf name;
* :func:`reference_step`, :func:`reference_state` -- the plain reference
  (``bench/configs/spikingformer_reference.py``) and its BatchNorm running
  statistics at their start;
* :func:`stats`, :func:`depth_stacked` -- the tokenizer's batch statistics
  that ``stats_gap`` compares, and the depth-stacked ``blocks`` leaves that
  count as one leaf per layer;
* :func:`step_flops`, :func:`kernel_work` -- operations and bytes of one
  train step, counted from the configuration's shapes (never from what a
  kernel happens to do).

``step_flops`` is the model's algorithm: 2 x the multiply-adds of the
tokenizer convolutions, the Q/K/V/Z and MLP projections, Q K^T, (Q K^T) V
and the head, times 3 for the forward and backward passes. Recomputation
is not counted, and no term depends on which implementation runs a site.

``kernel_work`` counts, for one kernel family, the work of the sites that
the step's execution plan gives to that family, as (flops, bytes) per
launch-sized piece, so that each piece's roofline time is the larger of
flops over peak and bytes over bandwidth:

* ``lif`` -- per neuron and time step, the forward reads X and writes S, and
  the backward reads the spike cotangent and the membrane potential and
  writes dX: 5 float32 words, 20 bytes. The few operations per element are
  negligible beside that, so these sites are bound by memory.
* ``spike_mm`` -- forward products with a {0,1} operand (the backward runs as
  dense XLA matmuls). A {0,1} operand counts 1 bit per element, the least
  any implementation must move; dense operands and outputs count 4 bytes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.configs.spikingformer_reference import make_step
from bench.traffic import Traffic
from bench.weights import leaf_name

THROUGHPUT = "images_per_s"
WORD = 4          # float32 bytes


def program_config(cell):
    """The program's config object for ``cell`` (sizes from the
    configuration file, policy and temporal tile from the traffic mix)."""
    from repro.core.lif import LIFConfig
    from repro.core.policy import named_policy
    from repro.core.spikingformer import SpikingFormerConfig

    return SpikingFormerConfig(
        **cell.model["model"], lif=LIFConfig(**cell.model["lif"]),
        time_chunk=cell.mix.get("time_chunk"),
        policy=named_policy(cell.mix["policy"]))


def optimizer_config(cell):
    from repro.train.optimizer import OptimizerConfig

    o = cell.model["optimizer"]
    return OptimizerConfig(**{f.name: o[f.name] for f in
                              dataclasses.fields(OptimizerConfig)
                              if f.name in o})


def program(cell, mesh):
    """((params, state, opt), step, plan) of the program for ``cell`` on
    ``mesh``: ``step(params, state, opt, images, labels) -> (params, state,
    opt, metrics)`` is pure and not yet jitted."""
    from repro.launch.train import build_spikingformer_state
    from repro.train.loop import make_train_step

    cfg, opt_cfg = program_config(cell), optimizer_config(cell)
    plan = {r.site: r.effective
            for r in cfg.execution_plan(int(cell.mix["batch"]))}
    params, state, opt, _ = build_spikingformer_state(cfg, mesh, opt_cfg)
    return ((params, state, opt), make_train_step(cfg, opt_cfg, mesh=mesh),
            plan)


class Images(Traffic):
    """The mix's image batches; each step takes (images, labels)."""

    def __init__(self, mix: dict, model: dict, seed: int):
        super().__init__(mix, model["model"], seed)
        self.samples = self.batch_size

    @staticmethod
    def inputs(placed: dict) -> tuple:
        return placed["images"], placed["labels"]


def traffic(mix: dict, model: dict, seed: int) -> Images:
    return Images(mix, model, seed)


def init(name: str, shape, dtype, key):
    """One parameter leaf, by the rule the configuration's ``init`` states
    for its name."""
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


def reference_step(model: dict, dtype=jnp.float32):
    """The plain reference's train step, with the program's signature,
    computed wholly in ``dtype``."""
    return make_step(model, dtype)


def reference_state(structs):
    """BatchNorm running statistics at their start (mean 0, variance 1),
    shaped and placed as ``structs``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jax.device_put(
            (jnp.ones if leaf_name(path).endswith("var") else jnp.zeros)(
                a.shape, a.dtype), a.sharding), structs)


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


def stats(state, model: dict):
    """The batch statistics (mean and variance) of every tokenizer stage's
    BatchNorm, read back from the running statistics after the first
    step."""
    return _batch_stats(state["tokenizer"], model["bn"]["momentum"])


def depth_stacked(name: str) -> bool:
    """Whether leaf ``name`` stacks one leaf per block over its first
    axis."""
    return name.startswith("blocks/")


def tokenizer_stages(m: dict) -> list[tuple[int, int, int]]:
    """(c_in, c_out, output side) of each eq. 4 stage of the configuration's
    ``model`` section ``m``."""
    n = m["image_size"] // m["patch_grid"]
    stages = max(1, n.bit_length() - 1)
    out, c_in, side = [], m["in_channels"], m["image_size"]
    for i in range(stages):
        c_out = m["d_model"] // 2 ** (stages - 1 - i)
        side //= 2
        out.append((c_in, c_out, side))
        c_in = c_out
    return out


def step_flops(model: dict, batch: int) -> float:
    m = model["model"]
    t, d, f = m["time_steps"], m["d_model"], m["d_ff"]
    n = m["patch_grid"] ** 2
    macs = sum(t * batch * side * side * 9 * c_in * c_out
               for c_in, c_out, side in tokenizer_stages(m))
    rows = t * batch * n
    per_layer = 4 * rows * d * d + 2 * rows * d * f + 2 * t * batch * n * n * d
    macs += m["num_layers"] * per_layer + batch * d * m["num_classes"]
    return 2.0 * 3.0 * macs


def _lif_pieces(m, batch, plan):
    t, d, f, layers = (m["time_steps"], m["d_model"], m["d_ff"],
                       m["num_layers"])
    rows = t * batch * m["patch_grid"] ** 2
    fused = lambda site: plan.get(site) == "fused_epilogue"  # noqa: E731
    elems = []
    for i, (_, c_out, side) in enumerate(tokenizer_stages(m)):
        if plan.get("tokenizer.lif") == "pallas" and not fused(
                f"tokenizer.conv.{i}"):
            elems.append(t * batch * side * side * c_out)
    per_layer = []
    if plan.get("pssa.lif") == "pallas":
        per_layer += [rows * d] * (2 if fused("pssa.qkv") else 5)
    if plan.get("smlp.lif") == "pallas":
        per_layer += [rows * d] + ([] if fused("smlp.a") else [rows * f])
    elems += per_layer * layers
    return [(0.0, 5.0 * WORD * e) for e in elems]


def _mm(m, c, k, spike_a=True, spike_b=False):
    """One forward (m, c) x (c, k) product with {0,1} operands as flagged."""
    a = m * c / 8 if spike_a else m * c * WORD
    b = c * k / 8 if spike_b else c * k * WORD
    return (2.0 * m * c * k, a + b + m * k * WORD)


def _spike_mm_pieces(m, batch, plan):
    t, d, f, h, layers = (m["time_steps"], m["d_model"], m["d_ff"],
                          m["n_heads"], m["num_layers"])
    n = m["patch_grid"] ** 2
    rows = t * batch * n
    spike_mm = lambda site: plan.get(site) == "pallas+spike_mm"  # noqa: E731
    pieces = []
    spike_in = m["spike_input"]
    for i, (c_in, c_out, side) in enumerate(tokenizer_stages(m)):
        if (plan.get(f"tokenizer.conv.{i}") == "pallas_packed" and spike_in
                and (9 * c_in) % 8 == 0):
            pieces += [_mm(batch * side * side, 9 * c_in, c_out)] * t
        spike_in = True
    layer = []
    if spike_mm("pssa.qkv"):
        layer += [_mm(rows, d, d)] * 3
    if spike_mm("pssa.proj"):
        layer += [_mm(rows, d, d)]
    if spike_mm("smlp.a"):
        layer += [_mm(rows, d, f)]
    if spike_mm("smlp.b"):
        layer += [_mm(rows, f, d)]
    dh = d // h
    if plan.get("attn_qk") == "pallas_packed" and dh % 8 == 0:
        layer += [_mm(n, dh, n, spike_b=True)] * (t * batch * h)
    if plan.get("attn_av") == "pallas_packed" and n % 8 == 0:
        layer += [_mm(dh, n, n)] * (t * batch * h)
    return pieces + layer * layers


KERNELS = {"lif": _lif_pieces, "spike_mm": _spike_mm_pieces}


def kernel_work(kind: str, model: dict, batch: int,
                plan: dict[str, str]) -> list[tuple[float, float]]:
    """(flops, bytes) of each piece of kernel family ``kind``'s work in one
    step, for ``plan`` (site -> implementation that runs it); none for a
    family this model does not count."""
    pieces = KERNELS.get(kind)
    return pieces(model["model"], batch, plan) if pieces else []
