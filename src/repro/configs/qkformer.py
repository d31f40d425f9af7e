"""Named QKFormer presets (looked up, with the Spikingformer's, through
``repro.configs.spikingformer.get_spikingformer_config``).

``"qkformer-10-768"`` is the published ImageNet HST-10-768 (Zhou et al.,
NeurIPS 2024, arXiv:2403.16552): stages of width 192 / 384 / 768 and depth
1 / 2 / 7, 8 heads, MLP ratio 4, T=4, 224x224 images, 1000 classes.
``"qkformer-smoke"`` is the CPU test size: the same three stages at widths
16 / 32 / 64 and depth 1 each, T=2, 32x32 images, 10 classes.
"""
from __future__ import annotations

from repro.core.qkformer import QKFormerConfig

QKFORMER_PRESETS: dict[str, QKFormerConfig] = {
    "qkformer-10-768": QKFormerConfig(),
    "qkformer-smoke": QKFormerConfig(
        widths=(16, 32, 64), depths=(1, 1, 1), time_steps=2, image_size=32,
        num_classes=10),
}
