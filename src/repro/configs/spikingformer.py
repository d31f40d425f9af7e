"""Named Spikingformer presets with execution-policy variants, and the one
lookup of every vision preset.

Mirrors :mod:`repro.configs.registry` for the paper's own model family:
``get_spikingformer_config("spikingformer-8-512")`` is the paper Table III
training target; ``"spikingformer-smoke"`` is the CPU test/bench size shared
by the parity tests and ``benchmarks/bench_model_table.py``. The same getter
resolves the QKFormer presets of :mod:`repro.configs.qkformer`
(:data:`VISION_PRESETS` holds both families).

Execution variants are spelled ``<name>@<policy>`` with a policy preset name
(``jnp``/``pallas``/``pallas-full``, e.g. ``spikingformer-smoke@pallas``) or
requested via the ``policy=`` kwarg — the same parameters load under any
policy. When neither is given, the ``REPRO_BACKEND`` environment variable
selects the policy preset (so ``REPRO_BACKEND=pallas-full pytest`` really
runs the full-Pallas path, it no longer silently falls back to jnp). The
PR 1 ``backend=``/``spike_mm=``/``interpret=`` kwargs still work as
deprecation shims.

Every lookup resolves the policy against the preset's shapes once
(:meth:`SpikingFormerConfig.execution_plan`) and logs any packed-kernel
fallback — per-site, at config time, never silently per call.
"""
from __future__ import annotations

import dataclasses
import os
from collections import ChainMap

from repro.configs.qkformer import QKFORMER_PRESETS
from repro.core.policy import (ExecutionPolicy, default_policy, log_fallbacks,
                               named_policy, policy_from_flags,
                               warn_deprecated_flags)
from repro.core.spikingformer import SpikingFormerConfig

SPIKINGFORMER_PRESETS: dict[str, SpikingFormerConfig] = {
    # Paper Table III: L=8, d=512, h=8, T=4, 224x224, P=14.
    "spikingformer-8-512": SpikingFormerConfig(),
    # ~1M-param synthetic-task size used by examples/train_spikingformer.py.
    "spikingformer-tiny": SpikingFormerConfig(
        num_layers=2, d_model=96, n_heads=4, d_ff=384, time_steps=4,
        image_size=32, patch_grid=8, num_classes=4),
    # CPU smoke size for parity tests and the model-level backend A/B.
    "spikingformer-smoke": SpikingFormerConfig(
        num_layers=2, d_model=64, n_heads=2, d_ff=128, time_steps=2,
        image_size=32, patch_grid=8, num_classes=10),
    # Pre-encoded spike-frame (DVS-style event data) smoke variant: the
    # first tokenizer stage consumes {0,1} frames over 8 input channels
    # (9*8 = 72, a multiple of 8), so under "pallas-full" *every* eq. 4
    # stage — stage 1 included — rides the bit-packed im2col spike conv.
    "spikingformer-smoke-dvs": SpikingFormerConfig(
        num_layers=2, d_model=64, n_heads=2, d_ff=128, time_steps=2,
        image_size=32, patch_grid=8, num_classes=10, in_channels=8,
        spike_input=True),
}


#: Every vision preset: the Spikingformer's and the QKFormer's (a live view
#: of both registries).
VISION_PRESETS = ChainMap(SPIKINGFORMER_PRESETS, QKFORMER_PRESETS)


def get_spikingformer_config(name: str, *,
                             policy: ExecutionPolicy | None = None,
                             time_chunk: int | None = None,
                             backend: str | None = None,
                             spike_mm: bool | None = None,
                             interpret: bool | None = None):
    """Look up a vision preset (:data:`VISION_PRESETS`), optionally
    rebinding the execution policy and, for a Spikingformer, the temporal
    tile length (``time_chunk``, see docs/SHARDING.md).

    Precedence: explicit legacy flags (deprecated) > ``policy=`` kwarg >
    ``@<policy>`` name suffix > ``REPRO_BACKEND`` env var > the preset's own
    policy (jnp).
    """
    if "@" in name:
        name, suffix = name.rsplit("@", 1)
        if policy is None:
            policy = named_policy(suffix)
    cfg = VISION_PRESETS[name]
    if time_chunk is not None:
        cfg = dataclasses.replace(cfg, time_chunk=time_chunk)
    if backend is not None or spike_mm is not None or interpret is not None:
        warn_deprecated_flags(
            "get_spikingformer_config(backend=/spike_mm=/interpret=)")
        cfg = cfg.with_policy(policy_from_flags(
            backend, spike_mm, interpret,
            base=policy if policy is not None else cfg.policy))
    elif policy is not None:
        cfg = cfg.with_policy(policy)
    elif os.environ.get("REPRO_BACKEND"):
        cfg = cfg.with_policy(default_policy())
    # Resolve packing constraints per site once, here — and report them.
    log_fallbacks(cfg.execution_plan())
    return cfg
