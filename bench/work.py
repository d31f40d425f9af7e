"""The roofline of a kernel family's work, whatever the model family.

Each model family counts its own work from the configuration's shapes
(``kernel_work`` and ``step_flops`` of ``bench/families/<family>.py``), as
(flops, bytes) per launch-sized piece; this turns pieces into the least
time the chip could take.
"""
from __future__ import annotations


def roofline_seconds(pieces, peaks: dict) -> float:
    """Least time for the pieces: per piece the larger of flops over peak
    and bytes over bandwidth."""
    return sum(max(fl / peaks["bf16_flops_per_s"],
                   by / peaks["hbm_bytes_per_s"]) for fl, by in pieces)
