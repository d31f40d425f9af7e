"""Synthetic data pipeline: deterministic, host-shardable, learnable.

The stream is a Markov-bigram language: a fixed (vocab, vocab) transition
table drawn from the dataset seed generates sequences whose next-token
distribution is low-entropy — a ~100M-param model visibly learns it within
a few hundred steps (used by examples/train_*.py and the integration tests).

Batches are produced per-host (each host generates only its shard of the
global batch, keyed by (seed, step, host_index)) and placed onto the mesh
with the global batch sharding — the standard multi-host input pattern.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.tracing import span


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 1234
    branching: int = 4          # candidate next-tokens per token


class SyntheticLM:
    """Deterministic bigram-process token stream."""

    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        rng = np.random.default_rng(cfg.seed)
        self.table = rng.integers(
            0, cfg.vocab_size,
            size=(cfg.vocab_size, cfg.branching)).astype(np.int32)

    def batch(self, step: int, host_index: int = 0,
              host_count: int = 1) -> dict[str, np.ndarray]:
        cfg = self.cfg
        local = cfg.global_batch // host_count
        with span("data.make"):
            rng = np.random.default_rng(
                (cfg.seed, step, host_index))
            toks = np.empty((local, cfg.seq_len + 1), np.int32)
            toks[:, 0] = rng.integers(0, cfg.vocab_size, size=local)
            choices = rng.integers(0, cfg.branching,
                                   size=(local, cfg.seq_len))
            for t in range(cfg.seq_len):
                toks[:, t + 1] = self.table[toks[:, t], choices[:, t]]
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def iterator(self, start_step: int = 0, host_index: int = 0,
                 host_count: int = 1) -> Iterator[dict[str, np.ndarray]]:
        step = start_step
        while True:
            yield self.batch(step, host_index, host_count)
            step += 1


@dataclasses.dataclass(frozen=True)
class VisionDataConfig:
    image_size: int
    num_classes: int
    global_batch: int
    channels: int = 3
    seed: int = 1234
    # Emit {0,1} spike frames (DVS-style event data) by thresholding the
    # blob images. Models with ``spike_input=True`` assert a binary input
    # contract — the bit-packed first-stage conv packs raw values — so
    # their synthetic stream must actually honour it.
    spikes: bool = False


class SyntheticVision:
    """Deterministic quadrant-blob classification stream (learnable).

    Each image is Gaussian noise plus a bright blob in one of four
    quadrants; the label is the quadrant. A ~1M-param Spikingformer drives
    the loss well below ln(4) within ~100 steps (used by
    examples/train_spikingformer.py and the vision launch driver).
    Host-shardable exactly like :class:`SyntheticLM`: each host generates
    only its slice of the global batch, keyed by (seed, step, host_index).
    """

    def __init__(self, cfg: VisionDataConfig):
        self.cfg = cfg

    def batch(self, step: int, host_index: int = 0,
              host_count: int = 1) -> dict[str, np.ndarray]:
        cfg = self.cfg
        local = cfg.global_batch // host_count
        size = cfg.image_size
        with span("data.make"):
            rng = np.random.default_rng((cfg.seed, step, host_index))
            labels = rng.integers(0, min(4, cfg.num_classes),
                                  size=local).astype(np.int32)
            imgs = rng.normal(0, 0.1, size=(local, size, size,
                                            cfg.channels)).astype(np.float32)
            half = size // 2
            for i, lab in enumerate(labels):
                y0 = (int(lab) // 2) * half
                x0 = (int(lab) % 2) * half
                imgs[i, y0:y0 + half, x0:x0 + half] += 1.0
            if cfg.spikes:   # blob pixels (~1.0) fire, background doesn't
                imgs = (imgs > 0.5).astype(np.float32)
        return {"images": imgs, "labels": labels}

    def iterator(self, start_step: int = 0, host_index: int = 0,
                 host_count: int = 1) -> Iterator[dict[str, np.ndarray]]:
        step = start_step
        while True:
            yield self.batch(step, host_index, host_count)
            step += 1


def place_batch(batch: dict[str, np.ndarray], mesh=None):
    """Put a host-local batch onto the mesh with global-batch sharding
    (host span ``data.place``)."""
    with span("data.place"):
        if mesh is None:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        from jax.sharding import NamedSharding, PartitionSpec as P
        batch_axes = tuple(a for a in ("pod", "data")
                           if a in mesh.axis_names)
        sharding = NamedSharding(mesh, P(batch_axes or None))
        return {k: jax.device_put(jnp.asarray(v), sharding)
                for k, v in batch.items()}
