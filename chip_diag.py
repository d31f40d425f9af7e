#!/usr/bin/env python3
"""The readings behind ``chip_smoke.py``'s two train-path bounds, on one
TPU chip, at its preset and batch:

    python3 chip_diag.py

1. step-0 loss spread — the jnp arm's step-0 loss, from the train step's
   own loss function, under rounding-level changes: 1e-6 input noise
   (three draws) and XLA matmul precision ``"highest"``. Its spread is
   why ``LOSS0_TOL`` is only a sanity bound.
2. planted faults — the composed gaps (``chip_smoke.composed_run``) of the
   ``pallas`` arm against ``jnp``: sound, and with each fault of
   :data:`PLANTS` planted in the row-tiled BN kernels. They are why
   ``COMPOSED_TOL`` separates a fault from rounding.

One line per reading; exits non-zero, like ``chip_smoke.py``, off the chip.
"""
from __future__ import annotations

import contextlib
import sys

import chip_smoke

#: ``chip_smoke.py``'s batch on a v5e (its ``[batch]`` lines).
BATCH = 8


@contextlib.contextmanager
def _patched(name: str, wrap):
    from repro.kernels import fused_bn

    orig = getattr(fused_bn, name)
    setattr(fused_bn, name, wrap(orig))
    try:
        yield
    finally:
        setattr(fused_bn, name, orig)


def dgamma_dropped():
    """BN backward returns no scale gradient: a VJP that loses one of its
    parameter cotangents."""
    def wrap(orig):
        def bn_bwd(*args, **kw):
            dx, dgamma, dbeta = orig(*args, **kw)
            return dx, dgamma * 0.0, dbeta
        return bn_bwd
    return _patched("bn_bwd", wrap)


def first_block_stats():
    """BN forward normalises with the statistics of the first row block
    only: a row-tiled reduction that does not accumulate over its grid."""
    def wrap(orig):
        def bn_fwd(x, gamma, beta, *, block_m=512, **kw):
            _, mu, sqrt_d = orig(x[:block_m], gamma, beta, block_m=block_m,
                                 **kw)
            y = (gamma.reshape(1, -1) * (x - mu) / sqrt_d
                 + beta.reshape(1, -1))
            return y.astype(x.dtype), mu, sqrt_d
        return bn_fwd
    return _patched("bn_fwd", wrap)


PLANTS = {"dgamma_dropped": dgamma_dropped,
          "first_block_stats": first_block_stats}


def loss_spread(cfg, batch: int) -> dict[str, float]:
    """Step-0 loss of ``cfg`` at ``batch`` under rounding-level changes."""
    import jax
    import numpy as np

    from repro.core.spikingformer import (init_spikingformer,
                                          spikingformer_loss_jit)
    from repro.train.data import SyntheticVision, VisionDataConfig

    params, state = init_spikingformer(jax.random.PRNGKey(0), cfg)
    data = SyntheticVision(VisionDataConfig(
        image_size=cfg.image_size, num_classes=cfg.num_classes,
        global_batch=batch, channels=cfg.in_channels,
        spikes=cfg.spike_input)).batch(0)
    rng = np.random.default_rng(2)
    variants = {"plain": (data["images"], "default")}
    for i in range(3):
        noise = 1e-6 * rng.standard_normal(data["images"].shape)
        variants[f"noise1e-6#{i}"] = (data["images"] + noise.astype(
            np.float32), "default")
    variants["highest"] = (data["images"], "highest")
    out = {}
    for name, (images, precision) in variants.items():
        with jax.default_matmul_precision(precision):
            loss, _ = spikingformer_loss_jit(params, state, images,
                                             data["labels"], cfg)
        out[name] = float(loss)
    return out


def plant_gaps(cfg, ref: dict, mesh, batch: int) -> dict[str, dict]:
    """Composed gaps of ``cfg`` against ``ref``, sound and per plant."""
    gaps = {"sound": chip_smoke.composed_gaps(
        chip_smoke.composed_run(cfg, batch, mesh), ref)}
    for name, plant in PLANTS.items():
        with plant():
            gaps[name] = chip_smoke.composed_gaps(
                chip_smoke.composed_run(cfg, batch, mesh), ref)
    return gaps


def main() -> int:
    dev = chip_smoke.check_device()
    chip_smoke._import_repro()
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_test_mesh

    print(f"[cache] {enable_compile_cache()}", flush=True)
    jnp_cfg = chip_smoke.arm_config("jnp")
    spread = loss_spread(jnp_cfg, BATCH)
    for name, loss in spread.items():
        print(f"[loss0] jnp {name}: {loss!r} diff {loss - spread['plain']!r}",
              flush=True)
    mesh = make_test_mesh(1, 1, devices=[dev])
    ref = chip_smoke.composed_run(jnp_cfg, BATCH, mesh)
    gaps = plant_gaps(chip_smoke.arm_config("pallas"), ref, mesh, BATCH)
    for name, g in gaps.items():
        print(f"[composed] pallas {name} vs jnp: {g}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
