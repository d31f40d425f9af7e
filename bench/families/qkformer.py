"""The QKFormer family: what the harness needs for a configuration that
names ``"family": "qkformer"``.

* :func:`program` -- the program's own train step, built as
  ``repro.launch.train.train_vision`` builds it: state from
  ``build_vision_state``, the pure step of
  ``repro.train.loop.make_train_step``, and the execution plan;
* :func:`traffic`, :func:`init`, :func:`reference_state` -- the
  Spikingformer family's: image batches from ``bench/traffic.py``, the same
  initialisation rules by leaf name, BatchNorm running statistics at their
  start;
* :func:`reference_step` -- the plain reference
  (``bench/configs/qkformer_reference.py``);
* :func:`stats`, :func:`depth_stacked` -- the first patch embedding's batch
  statistics that ``stats_gap`` compares, and the stage blocks' leaves,
  stacked over depth;
* :func:`step_flops` -- 2 x the multiply-adds of the patch embeddings'
  convolutions, the Q/K/(V)/projection and MLP matmuls, Q K^T and (Q K^T) V
  of the last stage, and the head, times 3 for the forward and backward
  passes, counted from the configuration's shapes; :func:`kernel_work`
  counts none, as no site of this model runs a kernel family the
  benchmark reads.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.configs.qkformer_reference import make_step
# The Spikingformer family's generator, initialisation, BN start and
# optimizer settings serve this family unchanged (re-exported for the
# harness). ``_batch_stats`` is that family's helper, private by name but
# shared on purpose: both families read batch statistics back from the
# running statistics the same way.
from bench.families.spikingformer import (  # noqa: F401
    THROUGHPUT, init, optimizer_config, reference_state, traffic)
from bench.families.spikingformer import _batch_stats as batch_stats
from repro.core.qkformer import QKFormerConfig


def program_config(cell) -> QKFormerConfig:
    """The program's config for ``cell`` (sizes from the configuration
    file, the policy from the traffic mix; the model has no temporal tile,
    so the mix's ``time_chunk`` must be null)."""
    from repro.core.lif import LIFConfig
    from repro.core.policy import named_policy

    if cell.mix.get("time_chunk") is not None:
        raise ValueError(f"{cell.name}: QKFormer runs no chunked LIF, "
                         "so the mix's time_chunk must be null")
    lif = dict(cell.model["lif"])
    attn = lif.pop("attn")
    return QKFormerConfig(
        **cell.model["model"], lif=LIFConfig(**lif),
        attn_lif=LIFConfig(**{**lif, **attn}),
        policy=named_policy(cell.mix["policy"]))


def program(cell, mesh):
    """((params, state, opt), step, plan) of the program for ``cell`` on
    ``mesh``."""
    from repro.launch.train import build_vision_state
    from repro.train.loop import make_train_step

    cfg, opt_cfg = program_config(cell), optimizer_config(cell)
    plan = {r.site: r.effective
            for r in cfg.execution_plan(int(cell.mix["batch"]))}
    params, state, opt, _ = build_vision_state(cfg, mesh, opt_cfg)
    return ((params, state, opt), make_train_step(cfg, opt_cfg, mesh=mesh),
            plan)


def reference_step(model: dict, dtype=jnp.float32):
    """The plain reference's train step, with the program's signature,
    computed wholly in ``dtype``."""
    return make_step(model, dtype)


def stats(state, model: dict):
    """Batch mean and variance of every BatchNorm of the first patch
    embedding (the stem, which sees the placed images), read back from the
    running statistics after the first step."""
    return batch_stats(state["embed"][0], model["bn"]["momentum"])


def depth_stacked(name: str) -> bool:
    return name.startswith("stages/")


def embed_convs(m: dict) -> list[tuple[int, int, int, int]]:
    """(kernel, c_in, c_out, output side before pooling) of every patch
    embedding convolution of the configuration's ``model`` section ``m``."""
    widths, side = m["widths"], m["image_size"]
    out = [(3, m["in_channels"], widths[0] // 2, side),
           (3, widths[0] // 2, widths[0], side // 2),
           (3, widths[0], widths[0], side // 4),
           (1, widths[0] // 2, widths[0], side // 4)]
    side //= 4
    for a, b in zip(widths, widths[1:]):
        out += [(3, a, b, side), (3, b, b, side), (1, a, b, side // 2)]
        side //= 2
    return out


def step_flops(model: dict, batch: int) -> float:
    m = model["model"]
    tb, r = m["time_steps"] * batch, m["mlp_ratio"]
    macs = sum(tb * side * side * k * k * c_in * c_out
               for k, c_in, c_out, side in embed_convs(m))
    side = m["image_size"] // 4
    last = len(m["widths"]) - 1
    for i, (w, depth) in enumerate(zip(m["widths"], m["depths"])):
        n = side * side
        rows = tb * n
        attn = (3 * rows * w * w if i < last
                else 4 * rows * w * w + 2 * tb * n * n * w)
        macs += depth * (attn + 2 * rows * w * r * w)
        side //= 2
    macs += batch * m["widths"][-1] * m["num_classes"]
    return 2.0 * 3.0 * macs


def kernel_work(kind: str, model: dict, batch: int,
                plan: dict[str, str]) -> list[tuple[float, float]]:
    return []
