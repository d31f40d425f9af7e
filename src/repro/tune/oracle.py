"""Analytic block-candidate oracle over the paper's dataflow model.

Our Pallas matmul family is output-stationary streaming the contraction
axis — an fp32 VMEM accumulator is revisited across the C grid dimension
while x re-fetches once per K-tile and w once per M-tile — i.e. exactly
the paper's ``OS_C`` dataflow with the *kernel block* playing the role of
the PE array tile. So a candidate ``(block_m, block_k, block_c)`` is
scored by eq. 26-28 + the uniform bandwidth bound
(:func:`~repro.core.energy.dataflow.mm_latency_cycles`) on an array of
``rows=block_m, cols=block_k``, plus a fixed per-grid-step overhead that
penalizes tiny ``block_c`` (more launches/revisits for the same MACs).
Candidates whose working set misses VMEM are infeasible and never ranked.

The oracle is pure arithmetic: deterministic, total-ordered (ties break
on the block tuple), and cheap enough to score every candidate — the
timed sweep then measures only the top-K (AutoST-style pruning).

For trailing-LIF sites the megakernel adds an *arm* axis: ``fused`` (one
launch, all T*M rows per program — feasible iff
``train_arm_vmem_bytes <= TRAIN_ARM_VMEM_BUDGET``) vs ``pipeline``
(M-tiled matmul + BN + SOMA, paying the (T, M, K) pre-activation HBM
round trip the fused arm never materializes).
"""
from __future__ import annotations

import dataclasses
import math

from repro.core.energy.constants import (ArrayConfig, DEFAULT_ARRAY,
                                         TPU_HBM_BW, TPU_PEAK_FLOPS_BF16)
from repro.core.energy.dataflow import (Dataflow, Inner, Outer,
                                        best_dataflow, mm_latency_cycles)
from repro.core.energy.workload import MMOp
from repro.tune.table import TunedBlocks
from repro.tune.workloads import SiteWorkload

#: Fixed cost charged per Pallas grid step (dispatch + pipeline refill of
#: the accumulator visit). Penalizes degenerate tiny blocks the bandwidth
#: terms alone would rank as free.
GRID_STEP_OVERHEAD_CYCLES = 128.0

#: Working-set budget for one grid step's VMEM residency (x + w tiles,
#: accumulator, output) — aligned with the megakernel's train-arm budget.
VMEM_BUDGET_BYTES = 12 * 2 ** 20

BLOCK_M_CANDIDATES = (128, 256, 512)
BLOCK_K_CANDIDATES = (128, 256, 512)
BLOCK_C_CANDIDATES = (128, 256, 512, 1024)

_OS_C = Dataflow(Inner.OS, Outer.C)


@dataclasses.dataclass(frozen=True)
class OracleCandidate:
    """One scored candidate; ``block_m is None`` on the fused train arm
    (its BN-stats constraint pins all rows to one program)."""

    block_m: int | None
    block_k: int
    block_c: int
    arm: str | None
    cycles: float
    vmem_bytes: int
    feasible: bool

    def as_tuned(self, *, measured_us: float | None = None,
                 sparsity: float | None = None) -> TunedBlocks:
        return TunedBlocks(block_m=self.block_m, block_k=self.block_k,
                           block_c=self.block_c, arm=self.arm,
                           oracle_cycles=self.cycles,
                           measured_us=measured_us, sparsity=sparsity)

    def sort_key(self):
        return (self.cycles, self.block_m or 0, self.block_k, self.block_c,
                self.arm or "")


def oracle_array() -> ArrayConfig:
    """TPU-flavoured scoring array: MXU-sized tiles at the roofline-derived
    clock, HBM bandwidth per cycle from the chip constants, generous VMEM
    banks (the candidate feasibility check guards capacity separately)."""
    freq = TPU_PEAK_FLOPS_BF16 / (128 * 128 * 2)
    return dataclasses.replace(
        DEFAULT_ARRAY, rows=128, cols=128, freq_hz=freq,
        sram_in_bytes=4 * 2 ** 20, sram_w_bytes=4 * 2 ** 20,
        sram_out_bytes=4 * 2 ** 20,
        dram_bytes_per_cycle=TPU_HBM_BW / freq,
        sram_bytes_per_cycle=2048.0)


def candidate_vmem_bytes(bm: int, bk: int, bc: int, in_bits: int) -> int:
    """One grid step's VMEM residency: x tile (packed = 1 bit/elem), w
    tile, fp32 accumulator scratch, output tile."""
    x = bm * bc * in_bits // 8 if in_bits >= 8 else bm * bc // 8
    return x + bc * bk * 4 + bm * bk * 4 + bm * bk * 4


def candidate_cycles(mm: MMOp, bm: int, bk: int, bc: int,
                     arr: ArrayConfig) -> float:
    """Latency of ``mm`` under OS_C with (bm, bk) as the stationary tile
    and the contraction streamed in bc-chunks."""
    eff_bm = max(1, min(bm, mm.B))
    eff_bk = max(1, min(bk, mm.K))
    eff_bc = max(1, min(bc, mm.C))
    tile_arr = dataclasses.replace(arr, rows=eff_bm, cols=eff_bk)
    base = mm_latency_cycles(mm, _OS_C, tile_arr)
    steps = (math.ceil(mm.B / eff_bm) * math.ceil(mm.K / eff_bk) *
             math.ceil(mm.C / eff_bc) * mm.count)
    return base + steps * GRID_STEP_OVERHEAD_CYCLES


def _pipeline_extra_cycles(mm: MMOp, arr: ArrayConfig) -> float:
    """The (T, M, K) fp16 pre-activation HBM round trip (write by the
    matmul, read back by BN/SOMA) that only the pipeline arm pays."""
    bits = 2 * mm.B * mm.K * mm.out_bits * mm.count
    return bits / 8 / arr.dram_bytes_per_cycle


def _snap_bc(bc: int, c: int, packed: bool) -> int:
    """Snap a block_c candidate the way the kernels do (a TPU-tileable
    divisor of C) so the oracle scores what would actually run."""
    from repro.kernels.spike_matmul import contraction_block

    return contraction_block(bc, c, packed)


def oracle_rank(wl: SiteWorkload, arr: ArrayConfig | None = None,
                top_k: int | None = None) -> list[OracleCandidate]:
    """Rank feasible block candidates for one site, best first.

    Empty for non-tunable sites (dense/jnp impls have no block knobs).
    The ordering is a pure function of the workload — stable across runs.
    """
    if not wl.tunable or wl.mm is None:
        return []
    arr = arr if arr is not None else oracle_array()
    mm = wl.mm
    in_bits = mm.in_bits
    cands: list[OracleCandidate] = []

    fused_site = wl.impl == "fused_epilogue"
    if not fused_site or not wl.trailing_lif:
        for bm in BLOCK_M_CANDIDATES:
            for bk in BLOCK_K_CANDIDATES:
                for bc in {_snap_bc(b, mm.C, in_bits == 1)
                           for b in BLOCK_C_CANDIDATES}:
                    vmem = candidate_vmem_bytes(min(bm, mm.B),
                                                min(bk, mm.K),
                                                min(bc, mm.C), in_bits)
                    cands.append(OracleCandidate(
                        bm, bk, bc, None,
                        candidate_cycles(mm, bm, bk, bc, arr), vmem,
                        vmem <= VMEM_BUDGET_BYTES))
    else:
        from repro.kernels.neuron_layer import (TRAIN_ARM_VMEM_BUDGET,
                                                train_arm_vmem_bytes)

        t = wl.shape[0]
        m = wl.shape[1]
        for bk in BLOCK_K_CANDIDATES:
            for bc in {_snap_bc(b, mm.C, wl.packed)
                       for b in BLOCK_C_CANDIDATES}:
                # fused arm: one launch, all T*M rows per program
                vmem = train_arm_vmem_bytes(t, m, mm.C, mm.K, wl.packed,
                                            block_k=bk, block_c=bc)
                cands.append(OracleCandidate(
                    None, bk, bc, "fused",
                    candidate_cycles(mm, mm.B, bk, bc, arr), int(vmem),
                    vmem <= TRAIN_ARM_VMEM_BUDGET))
                # pipeline arm: M-tiled matmul + pre-activation round trip
                for bm in BLOCK_M_CANDIDATES:
                    pvmem = candidate_vmem_bytes(min(bm, mm.B),
                                                 min(bk, mm.K),
                                                 min(bc, mm.C), in_bits)
                    cands.append(OracleCandidate(
                        bm, bk, bc, "pipeline",
                        candidate_cycles(mm, bm, bk, bc, arr)
                        + _pipeline_extra_cycles(mm, arr), pvmem,
                        pvmem <= VMEM_BUDGET_BYTES))

    # dedupe snapped duplicates, keep feasible, stable total order
    seen: set[tuple] = set()
    ranked = []
    for c in sorted(cands, key=OracleCandidate.sort_key):
        key = (c.block_m, c.block_k, c.block_c, c.arm)
        if key in seen or not c.feasible:
            continue
        seen.add(key)
        ranked.append(c)
    return ranked[:top_k] if top_k else ranked


def oracle_best_dataflow(wl: SiteWorkload) -> str:
    """The paper-model dataflow choice for this site's training MMs on the
    paper's 64x64 array (reported in the BENCH energy section)."""
    from repro.tune.workloads import training_mms

    mms = training_mms(wl)
    return best_dataflow(mms).name if mms else "-"
