"""Bit-packed spike x FP16/bf16 matmul Pallas kernel (E2ATST spike-MM unit).

The ASIC simplifies spike-operand MACs to additions; the TPU MXU cannot gate
multiplies per lane, so the paper's insight is realized on the *memory* side:
spikes travel HBM -> VMEM packed at 1 bit/element (16x less traffic than
bf16) and are unpacked to bf16 inside VMEM immediately before the MXU dot.

Packing is along the contraction dim C, in bit planes: C splits into groups
of :func:`pack_group` columns, and byte ``j`` of a group holds column
``b * group // 8 + j`` of that group in bit ``b``. Unpacking a group is then
eight shifts and a lane concatenation — no cross-lane interleave, which
Mosaic cannot lower — and a group of 1024 columns is one 128-lane byte row
per plane, so a contraction tile of whole groups is lane-aligned on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import resolve_interpret

#: Columns per bit-plane group when it divides C: 8 planes of 128 bytes.
PACK_GROUP = 1024


def pack_group(c: int) -> int:
    """Columns per bit-plane group of a C-wide spike operand:
    :data:`PACK_GROUP` when it divides C, otherwise all of C (one group)."""
    return PACK_GROUP if c % PACK_GROUP == 0 else c


def contraction_block(block_c: int, c: int, packed: bool) -> int:
    """Contraction tile for a kernel that accumulates over C: a divisor of
    C (a ragged last block would fold padding into every output tile) that
    the TPU can tile — a multiple of 128 lanes for a dense operand, of one
    pack group for a packed one — or all of C. The largest such tile
    <= ``block_c`` wins; failing that, the smallest."""
    if packed:
        assert c % 8 == 0, f"packed contraction dim {c} must be * of 8"
    step = pack_group(c) if packed else 128
    tiles = [bc for bc in range(step, c, step) if c % bc == 0] + [c]
    fitting = [bc for bc in tiles if bc <= block_c]
    return max(fitting) if fitting else tiles[0]


def spike_pack(spikes: jax.Array) -> jax.Array:
    """(..., C) {0,1} -> (..., C//8) uint8 in the bit-plane layout."""
    *lead, c = spikes.shape
    assert c % 8 == 0, f"contraction dim {c} must be a multiple of 8"
    g = pack_group(c)
    bits = spikes.reshape(*lead, c // g, 8, g // 8).astype(jnp.uint8)
    weights = (1 << jnp.arange(8, dtype=jnp.uint8))[:, None]
    packed = jnp.sum(bits * weights, axis=-2, dtype=jnp.uint8)
    return packed.reshape(*lead, c // 8)


def spike_unpack(packed: jax.Array, dtype=jnp.float32, *,
                 group_bytes: int | None = None) -> jax.Array:
    """(..., C//8) uint8 -> (..., C) in ``dtype``. ``group_bytes`` is the
    byte width of one pack group of the *whole* operand; a kernel that
    unpacks one contraction tile passes it, since the tile alone does not
    show how wide C is. The default reads it off ``packed`` itself."""
    c8 = packed.shape[-1]
    gb = group_bytes or pack_group(c8 * 8) // 8
    words = packed.astype(jnp.int32)   # Mosaic has no uint8 shift or cast
    planes = [(words[..., g0:g0 + gb] >> b) & 1
              for g0 in range(0, c8, gb) for b in range(8)]
    return jnp.concatenate(planes, axis=-1).astype(dtype)


def _spike_mm_kernel(sp_ref, w_ref, o_ref, acc_ref, *, n_cb, group_bytes):
    """Grid (M/bm, K/bk, C/bc); accumulate over the C axis in fp32 VMEM."""
    cb = pl.program_id(2)

    @pl.when(cb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = spike_unpack(sp_ref[...], dtype=w_ref.dtype,
                     group_bytes=group_bytes)             # (bm, bc) in VMEM
    acc_ref[...] += jnp.dot(x, w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(cb == n_cb - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_k", "block_c", "out_dtype", "interpret"))
def spike_matmul_packed(packed: jax.Array, w: jax.Array, *, block_m: int = 256,
                        block_k: int = 256, block_c: int = 512,
                        out_dtype=None,
                        interpret: bool | None = None) -> jax.Array:
    """packed: (M, C//8) uint8; w: (C, K) -> (M, K).

    MXU-aligned blocks (multiples of 128); the fp32 accumulator tile lives in
    a VMEM scratch buffer revisited across the C grid axis. ``interpret``
    defaults to ``None`` = auto (interpret mode everywhere except a real TPU
    backend); pass an explicit bool to force either mode.
    """
    m, c8 = packed.shape
    c, k = w.shape
    assert c == c8 * 8, f"packed C {c8 * 8} != weight C {c}"
    out_dtype = out_dtype or w.dtype
    bm, bk = min(block_m, m), min(block_k, k)
    bc = contraction_block(block_c, c, packed=True)
    grid = (pl.cdiv(m, bm), pl.cdiv(k, bk), pl.cdiv(c, bc))
    return pl.pallas_call(
        functools.partial(_spike_mm_kernel, n_cb=grid[2],
                          group_bytes=pack_group(c) // 8),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bc // 8), lambda i, j, cb: (i, cb)),
                  pl.BlockSpec((bc, bk), lambda i, j, cb: (cb, j))],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, cb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, k), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        interpret=resolve_interpret(interpret))(packed, w)


def spike_matmul(spikes: jax.Array, w: jax.Array, **kw) -> jax.Array:
    """Convenience: unpacked {0,1} spikes (M, C) x (C, K)."""
    return spike_matmul_packed(spike_pack(spikes), w, **kw)


# ---------------------------------------------------------------------------
# Batched variant for the PSSA attention einsums: the (QK^T)V contractions
# are per-(T, B, head) matmuls, so the grid grows a leading batch axis.
# ---------------------------------------------------------------------------

def _spike_bmm_kernel(sp_ref, w_ref, o_ref, acc_ref, *, n_cb, group_bytes):
    """Grid (G, M/bm, K/bk, C/bc); fp32 VMEM accumulator over the C axis."""
    cb = pl.program_id(3)

    @pl.when(cb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = spike_unpack(sp_ref[0], dtype=w_ref.dtype,
                     group_bytes=group_bytes)             # (bm, bc) in VMEM
    acc_ref[...] += jnp.dot(x, w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(cb == n_cb - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_k", "block_c", "out_dtype", "interpret"))
def spike_matmul_packed_batched(packed: jax.Array, w: jax.Array, *,
                                block_m: int = 256, block_k: int = 256,
                                block_c: int = 512, out_dtype=None,
                                interpret: bool | None = None) -> jax.Array:
    """packed: (G, M, C//8) uint8; w: (G, C, K) -> (G, M, K).

    Same accumulator scheme as :func:`spike_matmul_packed` with one grid axis
    per batch element; either operand may be the spike side upstream (the
    attention AV product packs V^T and feeds attn^T here as ``w``).
    """
    g, m, c8 = packed.shape
    gw, c, k = w.shape
    assert gw == g, f"batch mismatch {gw} != {g}"
    assert c == c8 * 8, f"packed C {c8 * 8} != weight C {c}"
    out_dtype = out_dtype or w.dtype
    bm, bk = min(block_m, m), min(block_k, k)
    bc = contraction_block(block_c, c, packed=True)
    grid = (g, pl.cdiv(m, bm), pl.cdiv(k, bk), pl.cdiv(c, bc))
    return pl.pallas_call(
        functools.partial(_spike_bmm_kernel, n_cb=grid[3],
                          group_bytes=pack_group(c) // 8),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bm, bc // 8),
                               lambda gi, i, j, cb: (gi, i, cb)),
                  pl.BlockSpec((1, bc, bk),
                               lambda gi, i, j, cb: (gi, cb, j))],
        out_specs=pl.BlockSpec((1, bm, bk), lambda gi, i, j, cb: (gi, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, k), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        interpret=resolve_interpret(interpret))(packed, w)


def spike_matmul_batched(spikes: jax.Array, w: jax.Array, **kw) -> jax.Array:
    """Convenience: unpacked {0,1} spikes (G, M, C) x (G, C, K)."""
    return spike_matmul_packed_batched(spike_pack(spikes), w, **kw)


# ---------------------------------------------------------------------------
# Kernel-contract declarations (repro.analysis.contracts): abstract-geometry
# builders + the (op, impl) dispatch pairs whose sites launch these kernels.
# ---------------------------------------------------------------------------

from repro.kernels import ref as _ref  # noqa: E402
from repro.kernels.contract import (KernelContract, SkipCase,  # noqa: E402
                                    declare_contract)


def _build_spike_matmul(case):
    if case.c % 8 != 0:
        raise SkipCase(f"contraction {case.c} % 8 != 0 -> dense fallback")
    f = jax.ShapeDtypeStruct
    args = (f((case.t * case.m, case.c), case.dtype),
            f((case.c, case.k), case.dtype))
    return args, {}, {}


def _build_spike_matmul_batched(case):
    if case.c % 8 != 0:
        raise SkipCase(f"contraction {case.c} % 8 != 0 -> jnp einsum")
    f = jax.ShapeDtypeStruct
    args = (f((case.t, case.m, case.c), case.dtype),
            f((case.t, case.c, case.k), case.dtype))
    return args, {}, {}


declare_contract(KernelContract(
    name="spike_matmul", fn=spike_matmul, build=_build_spike_matmul,
    ref=_ref.spike_matmul_ref,
    serves=(("linear_bn", "pallas+spike_mm"),)))

declare_contract(KernelContract(
    name="spike_matmul_batched", fn=spike_matmul_batched,
    build=_build_spike_matmul_batched, ref=_ref.spike_matmul_batched_ref,
    serves=(("attn_qk", "pallas_packed"), ("attn_av", "pallas_packed"))))
