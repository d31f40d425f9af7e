"""The chips a run may use, and their published peaks."""
from __future__ import annotations

from bench.cells import BENCH, load_json


class NoChip(SystemExit):
    """The run cannot measure here; exits non-zero with the reason."""


def require_tpus(chips: int):
    """The first ``chips`` TPU devices, or :class:`NoChip` naming what JAX
    found instead."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"bench: needs {chips} TPU chip(s); JAX found platform "
                     f"{platform!r} ({devices[0].device_kind}); no result")
    if len(devices) < chips:
        raise NoChip(f"bench: needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)}; no result")
    return devices[:chips]


def peaks_for(kind: str) -> dict:
    """Published peaks of ``kind`` from ``bench/peaks.json``; a device that
    is not in the table is an error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise NoChip(f"bench: no published peaks for device kind {kind!r} "
                     f"in bench/peaks.json (has {sorted(table)}); no result")
    return table[kind]
