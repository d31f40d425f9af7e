"""Single-launch neuron-layer megakernel: matmul + BN + SOMA in one kernel.

E2ATST's temporal-spatial dataflow keeps the membrane potential local to the
compute unit and reuses the layer weights across all T time steps instead of
round-tripping the (T, M, K) pre-activation through memory. The previous
pipeline realized each piece separately — spike matmul, fused BN, fused SOMA
— as three ``pallas_call`` launches with two full HBM-materialized
intermediates between them. This module collapses a whole "neuron layer"
(the Conv1DBN -> SN pair, or one im2col'd eq. 4 tokenizer stage) into ONE
kernel:

* the (bit-packed or dense) spike matmul accumulates ``x_t @ w`` for every
  time step into an fp32 VMEM scratch tile, revisited across the contraction
  grid axis — the weight tile is fetched once per (c, k) block and reused by
  all T steps, the paper's weight-reuse axis;
* BatchNorm is applied in the same VMEM visit: batch statistics are computed
  in-kernel in train mode (the feature grid axis owns all T*M rows, exactly
  like :mod:`repro.kernels.fused_bn`), and in eval mode the caller folds BN
  into the weights/bias RTFormer-style so the kernel only adds a bias;
* the SOMA membrane update (eq. 11) runs over the unrolled T loop with the
  (U, S) carry held in VMEM registers, emitting spikes directly — the
  pre-activation never exists in HBM.

The differentiable wrappers (``neuron_layer_train_op`` /
``neuron_layer_eval_op``) live in :mod:`repro.kernels.ops`; their backward
*replays* the recomputed pre-activation through the existing GRAD kernel
(eq. 12) and the fused BN backward (eq. 19-23), so no per-step residuals are
stored between FP and BP — the temporal-blocking memory profile comes built
in.

Layouts: ``x`` is time-major (T, M, C) with M = B*N (or B*Ho*Wo) rows
folded; ``w`` is (C, K). Train mode tiles (K, C) and owns all T*M rows per
program (the BN-statistics constraint); eval mode additionally tiles M.
The train arm's VMEM grows with T*M (see :func:`train_arm_vmem_bytes`);
where it outgrows the compiler's limit the caller plans the M-tiled
pipeline instead.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.backend import resolve_interpret
from repro.kernels.spike_matmul import (contraction_block, pack_group,
                                       spike_pack, spike_unpack)


def _accumulate(x_ref, w_ref, acc_ref, *, group_bytes, time_steps):
    """acc[t] += x_t @ w for every unrolled time step (one (c, k) block);
    ``group_bytes`` is the pack group width of a packed x, None if dense."""
    w = w_ref[...]
    for t in range(time_steps):
        xt = (spike_unpack(x_ref[t], dtype=w.dtype, group_bytes=group_bytes)
              if group_bytes else x_ref[t])
        acc_ref[t] += jnp.dot(xt, w, preferred_element_type=jnp.float32)


def _soma(acc_ref, s_ref, y_of_t, *, alpha, th_fire, time_steps):
    """Unrolled eq. 11 over the accumulated tiles; (U, S) stay in VMEM."""
    u = jnp.zeros_like(acc_ref[0])
    s = jnp.zeros_like(u)
    for t in range(time_steps):
        u = alpha * u * (1.0 - s) + y_of_t(t)
        s = (u >= th_fire).astype(u.dtype)
        s_ref[t] = s.astype(s_ref.dtype)


def _nl_train_kernel(x_ref, w_ref, gamma_ref, beta_ref, s_ref, mu_ref,
                     var_ref, acc_ref, *, n_cb, group_bytes, alpha, th_fire,
                     eps, time_steps, m_rows):
    """Grid (K/bk, C/bc): accumulate over C, then BN-stats + SOMA epilogue.

    Each program owns all T*M rows of its feature block, so the batch
    statistics (eq. 13-15, over T*M) are computed in the same VMEM visit
    that normalizes and fires — the paper's single-pass BN, fused behind
    the matmul instead of launched after it.
    """
    cb = pl.program_id(1)

    @pl.when(cb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref, w_ref, acc_ref, group_bytes=group_bytes,
                time_steps=time_steps)

    @pl.when(cb == n_cb - 1)
    def _epilogue():
        z = acc_ref[...]                                       # (T, M, bk)
        denom = time_steps * m_rows
        mu = jnp.sum(jnp.sum(z, axis=0), axis=0, keepdims=True) / denom
        ex2 = jnp.sum(jnp.sum(z * z, axis=0), axis=0,
                      keepdims=True) / denom                   # eq. 14
        var = jnp.maximum(ex2 - mu * mu, 0.0)                  # eq. 15
        sqrt_d = jnp.sqrt(var + eps)                           # eq. 16
        gamma = gamma_ref[...].astype(jnp.float32)
        beta = beta_ref[...].astype(jnp.float32)
        _soma(acc_ref, s_ref,
              lambda t: gamma * (z[t] - mu) / sqrt_d + beta,   # eq. 17-18
              alpha=alpha, th_fire=th_fire, time_steps=time_steps)
        mu_ref[...] = mu
        var_ref[...] = var


def _nl_eval_kernel(x_ref, w_ref, b_ref, s_ref, acc_ref, *, n_cb,
                    group_bytes, alpha, th_fire, time_steps):
    """Grid (M/bm, K/bk, C/bc): BN pre-folded into (w, bias) by the caller
    (fixed running statistics), so the epilogue is bias + SOMA."""
    cb = pl.program_id(2)

    @pl.when(cb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref, w_ref, acc_ref, group_bytes=group_bytes,
                time_steps=time_steps)

    @pl.when(cb == n_cb - 1)
    def _epilogue():
        bias = b_ref[...].astype(jnp.float32)
        _soma(acc_ref, s_ref, lambda t: acc_ref[t] + bias,
              alpha=alpha, th_fire=th_fire, time_steps=time_steps)


#: The v5e compiler's default scoped-VMEM limit per kernel, which
#: :func:`train_arm_vmem_bytes` is checked against. Interpret mode has no
#: such limit, so the guard only matters when lowering to Mosaic.
TRAIN_ARM_VMEM_BUDGET: int = 16 * 2 ** 20


def _lanes(n: int) -> int:
    """``n`` rounded up to whole 128-lane vregs (VMEM pads the minor dim)."""
    return -(-n // 128) * 128


def train_arm_vmem_bytes(t: int, m: int, c: int, k: int, packed: bool, *,
                         block_k: int = 256, block_c: int = 256) -> int:
    """Estimated per-program VMEM of the train-mode megakernel: per row of
    the T*M rows one program owns (the BN-statistics constraint), the fp32
    accumulator and the double-buffered spike output (each T x bk), the
    double-buffered x tile and, packed, the int32 words and fp32 spikes
    one x_t unpacks to; plus the
    double-buffered (bc, bk) weight tile. Minor dims pad to 128 lanes.

    Checked against Mosaic for v5e at 23 ``spikingformer-8-512`` site
    geometries (T=4) and one ``spikingformer-smoke`` geometry (T=2, M=4096,
    C=K=64): every one estimated under :data:`TRAIN_ARM_VMEM_BUDGET`
    compiled, and every one that failed was estimated over it; two that
    compiled were estimated over it (the estimate errs towards the
    pipeline). Callers compare against the budget
    to decide, in the plan and per call, whether the single-launch train
    arm fits or the M-tiled pipeline runs instead."""
    bk = min(block_k, k)
    bc = contraction_block(block_c, c, packed)
    x_row = t * (_lanes(bc // 8) if packed else _lanes(bc) * 4)
    # Unpacking widens the bytes to int32 words, then to fp32 spikes.
    unpacked_row = (_lanes(bc // 8) + _lanes(bc)) * 4 if packed else 0
    per_row = 3 * t * _lanes(bk) * 4 + 2 * x_row + unpacked_row
    return m * per_row + 2 * bc * _lanes(bk) * 4


def _group_bytes(c: int, packed: bool) -> int | None:
    return pack_group(c) // 8 if packed else None


@functools.partial(jax.jit, static_argnames=(
    "alpha", "th_fire", "eps", "packed", "block_k", "block_c", "interpret"))
def neuron_layer_train(x: jax.Array, w: jax.Array, gamma: jax.Array,
                       beta: jax.Array, *, alpha: float = 0.5,
                       th_fire: float = 1.0, eps: float = 1e-5,
                       packed: bool = False, block_k: int = 256,
                       block_c: int = 256,
                       interpret: bool | None = None):
    """Train-mode neuron layer: x (T, M, C) @ w (C, K) -> BN (batch stats)
    -> SOMA, one launch. Returns ``(spikes (T, M, K), mu (1, K), var
    (1, K))`` — the fp32 batch statistics feed the caller's running-stat
    blend, exactly like ``ops.bn_train_op``.

    ``packed=True`` bit-packs the {0,1} ``x`` along C (8 spikes/byte) so it
    crosses HBM at 1 bit/element and is unpacked inside VMEM right before
    the MXU dot; C must be a multiple of 8.
    """
    t, m, c = x.shape
    cw, k = w.shape
    assert cw == c, f"weight contraction {cw} != input {c}"
    bk = min(block_k, k)
    bc = contraction_block(block_c, c, packed)
    xin = spike_pack(x) if packed else x
    xspec = pl.BlockSpec((t, m, bc // 8 if packed else bc),
                         lambda j, cb: (0, 0, cb))
    vec = pl.BlockSpec((1, bk), lambda j, cb: (0, j))
    grid = (pl.cdiv(k, bk), pl.cdiv(c, bc))
    kernel = functools.partial(_nl_train_kernel, n_cb=grid[1],
                               group_bytes=_group_bytes(c, packed),
                               alpha=alpha, th_fire=th_fire, eps=eps,
                               time_steps=t, m_rows=m)
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[xspec,
                  pl.BlockSpec((bc, bk), lambda j, cb: (cb, j)),
                  vec, vec],
        out_specs=[pl.BlockSpec((t, m, bk), lambda j, cb: (0, 0, j)),
                   vec, vec],
        out_shape=[jax.ShapeDtypeStruct((t, m, k), x.dtype),
                   jax.ShapeDtypeStruct((1, k), jnp.float32),
                   jax.ShapeDtypeStruct((1, k), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t, m, bk), jnp.float32)],
        interpret=resolve_interpret(interpret))(
            xin, w, gamma.reshape(1, k), beta.reshape(1, k))


@functools.partial(jax.jit, static_argnames=(
    "alpha", "th_fire", "packed", "block_m", "block_k", "block_c",
    "interpret"))
def neuron_layer_eval(x: jax.Array, w: jax.Array, bias: jax.Array, *,
                      alpha: float = 0.5, th_fire: float = 1.0,
                      packed: bool = False, block_m: int = 256,
                      block_k: int = 256, block_c: int = 256,
                      interpret: bool | None = None) -> jax.Array:
    """Eval-mode neuron layer: x (T, M, C) @ w (C, K) + bias -> SOMA, one
    launch; BN is already folded into ``(w, bias)`` (RTFormer-style, exact
    for running statistics), so the grid can tile M too. Returns spikes
    (T, M, K)."""
    t, m, c = x.shape
    cw, k = w.shape
    assert cw == c, f"weight contraction {cw} != input {c}"
    bm, bk = min(block_m, m), min(block_k, k)
    bc = contraction_block(block_c, c, packed)
    xin = spike_pack(x) if packed else x
    xspec = pl.BlockSpec((t, bm, bc // 8 if packed else bc),
                         lambda i, j, cb: (0, i, cb))
    grid = (pl.cdiv(m, bm), pl.cdiv(k, bk), pl.cdiv(c, bc))
    kernel = functools.partial(_nl_eval_kernel, n_cb=grid[2],
                               group_bytes=_group_bytes(c, packed),
                               alpha=alpha, th_fire=th_fire, time_steps=t)
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[xspec,
                  pl.BlockSpec((bc, bk), lambda i, j, cb: (cb, j)),
                  pl.BlockSpec((1, bk), lambda i, j, cb: (0, j))],
        out_specs=pl.BlockSpec((t, bm, bk), lambda i, j, cb: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((t, m, k), x.dtype),
        scratch_shapes=[pltpu.VMEM((t, bm, bk), jnp.float32)],
        interpret=resolve_interpret(interpret))(
            xin, w, bias.reshape(1, k).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Kernel-contract declarations (repro.analysis.contracts). The megakernel
# runs dense or bit-packed; the packed arm requires C % 8 == 0 (the callers
# demote to the dense arm otherwise, logged), so both arms are declared via
# the case's ``packed`` flag rather than a skip.
# ---------------------------------------------------------------------------

from repro.kernels import ref as _ref  # noqa: E402
from repro.kernels.contract import (KernelContract, SkipCase,  # noqa: E402
                                    declare_contract)

_NL_SERVES = (("linear_bn", "fused_epilogue"), ("conv", "fused_epilogue"))


def _nl_packed(case) -> bool:
    if case.packed and case.c % 8 != 0:
        raise SkipCase(f"packed arm with C {case.c} % 8 != 0 never launches")
    return case.packed


def _build_nl_train(case):
    f = jax.ShapeDtypeStruct
    packed = _nl_packed(case)
    args = (f((case.t, case.m, case.c), case.dtype),
            f((case.c, case.k), case.dtype), f((case.k,), case.dtype),
            f((case.k,), case.dtype))
    return args, {"packed": packed}, {}


def _build_nl_eval(case):
    f = jax.ShapeDtypeStruct
    packed = _nl_packed(case)
    args = (f((case.t, case.m, case.c), case.dtype),
            f((case.c, case.k), case.dtype), f((case.k,), jnp.float32))
    return args, {"packed": packed}, {}


declare_contract(KernelContract(
    name="neuron_layer_train", fn=neuron_layer_train, build=_build_nl_train,
    ref=_ref.neuron_layer_train_ref, serves=_NL_SERVES))

declare_contract(KernelContract(
    name="neuron_layer_eval", fn=neuron_layer_eval, build=_build_nl_eval,
    ref=_ref.neuron_layer_eval_ref, serves=_NL_SERVES))
