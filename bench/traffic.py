"""The one traffic generator: a training job's batches, from a mix file.

A mix file (``bench/traffic/<name>.json``) gives the global batch, the mesh,
the execution policy preset, the temporal tile and how images are drawn.
Two kinds of image:

* ``blobs`` -- Gaussian noise (``noise_std``) plus a bright square
  (``blob_amp``) in one of four quadrants; the label is the quadrant. This
  is the program's ``SyntheticVision`` stream (``repro/train/data.py``)
  copied here so that the yardstick cannot change under a later PR.
* ``events`` -- DVS-style {0,1} event frames over the configuration's
  polarity channels: each pixel fires with ``background_rate``, and with
  ``blob_rate`` inside the labelled quadrant, so no two samples are alike.

Every batch is a function of (seed, step) alone, so a run and the
reference see the same inputs, and two runs of one seed the same work.
"""
from __future__ import annotations

import numpy as np


def seed_words(seed: int) -> list[int]:
    """``seed`` as non-negative 32-bit words (seeds may exceed 32 bits)."""
    s = seed % 2**64
    return [s & 0xFFFFFFFF, s >> 32]


class Traffic:
    """Batches of one mix for one model configuration."""

    def __init__(self, mix: dict, model: dict, seed: int):
        self.batch_size = int(mix["batch"])
        self.images = dict(mix["images"])
        self.size = int(model["image_size"])
        self.channels = int(model["in_channels"])
        self.seed = seed_words(seed)
        if self.images["kind"] not in ("blobs", "events"):
            raise ValueError(f"unknown image kind {self.images['kind']!r}")

    def batch(self, step: int) -> dict[str, np.ndarray]:
        rng = np.random.default_rng(self.seed + [step])
        b, size, c = self.batch_size, self.size, self.channels
        spec = self.images
        labels = rng.integers(0, spec["label_classes"], size=b).astype(
            np.int32)
        half = size // 2
        if spec["kind"] == "blobs":
            imgs = rng.standard_normal((b, size, size, c), dtype=np.float32)
            imgs *= np.float32(spec["noise_std"])
            for i, lab in enumerate(labels):
                y0, x0 = (int(lab) // 2) * half, (int(lab) % 2) * half
                imgs[i, y0:y0 + half, x0:x0 + half] += np.float32(
                    spec["blob_amp"])
        else:
            rate = np.full((b, size, size, 1), spec["background_rate"],
                           np.float32)
            for i, lab in enumerate(labels):
                y0, x0 = (int(lab) // 2) * half, (int(lab) % 2) * half
                rate[i, y0:y0 + half, x0:x0 + half] = spec["blob_rate"]
            imgs = (rng.random((b, size, size, c), dtype=np.float32)
                    < rate).astype(np.float32)
        return {"images": imgs, "labels": labels}
