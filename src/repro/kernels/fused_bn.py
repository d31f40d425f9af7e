"""Fused BatchNorm FP/BP Pallas kernels (E2ATST Fig. 5-6, eq. 13-23).

The ASIC deeply pipelines dedicated BN datapaths (4 adders / 3 muls / 2 divs /
sqrt per lane) with the paper's own E[x^2] - mu^2 formulation: the
statistics need only running sums, never a centred second pass.

Layout: x is (M, D); BN is per-feature (last axis). Each direction is two
row-tiled launches, so VMEM holds one (block_m, block_d) tile whatever M is
(M = T*B*H*W reaches 50176*B rows at the paper's first tokenizer stage):

* a reduction over a (D/bd, M/bm) grid accumulates the per-feature sums
  into (1, bd) outputs revisited along the row axis, and closes them into
  the statistics at the last row block;
* an elementwise pass over an (M/bm, D/bd) grid applies them.

Rows past M in a ragged last block are masked out of every sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backend import resolve_interpret


def _row_mask(x, block_m, m_rows):
    """x with the rows past ``m_rows`` (ragged last row block) zeroed; the
    row block index is grid axis 1 of the reduction launches."""
    rows = (pl.program_id(1) * block_m
            + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0))
    return jnp.where(rows < m_rows, x, 0.0)


def _bn_stats_kernel(x_ref, mu_ref, sqrt_ref, *, eps, m_rows, block_m):
    """Grid (D/bd, M/bm): sum and sum of squares over row blocks."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        mu_ref[...] = jnp.zeros_like(mu_ref)
        sqrt_ref[...] = jnp.zeros_like(sqrt_ref)

    xf = _row_mask(x_ref[...].astype(jnp.float32), block_m, m_rows)
    mu_ref[...] += jnp.sum(xf, axis=0, keepdims=True)
    sqrt_ref[...] += jnp.sum(xf * xf, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        mu = mu_ref[...] / m_rows                                 # eq. 13
        ex2 = sqrt_ref[...] / m_rows                              # eq. 14
        var = jnp.maximum(ex2 - mu * mu, 0.0)                     # eq. 15
        mu_ref[...] = mu
        sqrt_ref[...] = jnp.sqrt(var + eps)                       # eq. 16


def _bn_norm_kernel(x_ref, gamma_ref, beta_ref, mu_ref, sqrt_ref, y_ref):
    n = x_ref[...].astype(jnp.float32) - mu_ref[...]              # eq. 17
    y = (gamma_ref[...].astype(jnp.float32) * n / sqrt_ref[...]
         + beta_ref[...].astype(jnp.float32))                     # eq. 18
    y_ref[...] = y.astype(y_ref.dtype)


def _bn_bwd_sums_kernel(g_ref, x_ref, gamma_ref, mu_ref, sqrt_ref, sn_ref,
                        sm_ref, smn_ref, sg_ref, *, m_rows, block_m):
    """Grid (D/bd, M/bm): the eq. 20-22 sums over row blocks."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        for r in (sn_ref, sm_ref, smn_ref, sg_ref):
            r[...] = jnp.zeros_like(r)

    g = _row_mask(g_ref[...].astype(jnp.float32), block_m, m_rows)
    n = _row_mask(x_ref[...].astype(jnp.float32) - mu_ref[...], block_m,
                  m_rows)
    mi = gamma_ref[...].astype(jnp.float32) * g / sqrt_ref[...]   # eq. 19
    sn_ref[...] += jnp.sum(n, axis=0, keepdims=True)              # eq. 20
    sm_ref[...] += jnp.sum(mi, axis=0, keepdims=True)
    smn_ref[...] += jnp.sum(mi * n, axis=0, keepdims=True)
    sg_ref[...] += jnp.sum(g, axis=0, keepdims=True)              # eq. 22


def _bn_bwd_dx_kernel(g_ref, x_ref, gamma_ref, mu_ref, sqrt_ref, sn_ref,
                      sm_ref, smn_ref, dx_ref, *, m_rows):
    g = g_ref[...].astype(jnp.float32)
    n = x_ref[...].astype(jnp.float32) - mu_ref[...]
    sqrt_d = sqrt_ref[...]
    mi = gamma_ref[...].astype(jnp.float32) * g / sqrt_d          # eq. 19
    s_n, s_m, s_mn = sn_ref[...], sm_ref[...], smn_ref[...]
    sq2 = sqrt_d * sqrt_d
    dx = (mi - n * s_mn / (m_rows * sq2)
          + s_n * s_mn / (sq2 * m_rows * m_rows) - s_m / m_rows)  # eq. 23
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _specs(m, d, block_m, block_d):
    """Tiles, grids and BlockSpecs shared by the four launches: ``red_*``
    index the (D/bd, M/bm) reduction grid, ``ew_*`` the (M/bm, D/bd)
    elementwise grid."""
    bm, bd = min(block_m, m), min(block_d, d)
    red_grid = (pl.cdiv(d, bd), pl.cdiv(m, bm))
    ew_grid = (pl.cdiv(m, bm), pl.cdiv(d, bd))
    return dict(
        bm=bm, red_grid=red_grid, ew_grid=ew_grid,
        red_tile=pl.BlockSpec((bm, bd), lambda j, i: (i, j)),
        red_vec=pl.BlockSpec((1, bd), lambda j, i: (0, j)),
        ew_tile=pl.BlockSpec((bm, bd), lambda i, j: (i, j)),
        ew_vec=pl.BlockSpec((1, bd), lambda i, j: (0, j)))


@functools.partial(jax.jit, static_argnames=("eps", "block_m", "block_d",
                                             "interpret"))
def bn_fwd(x: jax.Array, gamma: jax.Array, beta: jax.Array, *,
           eps: float = 1e-5, block_m: int = 512, block_d: int = 512,
           interpret: bool | None = None):
    """x: (M, D) -> (y (M, D), mu (1, D), sqrt_d (1, D)). ``interpret=None``
    = auto: interpret mode everywhere except a real TPU backend."""
    interpret = resolve_interpret(interpret)
    m, d = x.shape
    sp = _specs(m, d, block_m, block_d)
    vec = jax.ShapeDtypeStruct((1, d), jnp.float32)
    mu, sqrt_d = pl.pallas_call(
        functools.partial(_bn_stats_kernel, eps=eps, m_rows=m,
                          block_m=sp["bm"]),
        grid=sp["red_grid"], in_specs=[sp["red_tile"]],
        out_specs=[sp["red_vec"]] * 2, out_shape=[vec, vec],
        interpret=interpret)(x)
    y = pl.pallas_call(
        _bn_norm_kernel, grid=sp["ew_grid"],
        in_specs=[sp["ew_tile"]] + [sp["ew_vec"]] * 4,
        out_specs=sp["ew_tile"],
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        interpret=interpret)(x, gamma.reshape(1, d), beta.reshape(1, d),
                             mu, sqrt_d)
    return y, mu, sqrt_d


@functools.partial(jax.jit, static_argnames=("block_m", "block_d",
                                             "interpret"))
def bn_bwd(g: jax.Array, x: jax.Array, gamma: jax.Array, mu: jax.Array,
           sqrt_d: jax.Array, *, block_m: int = 512, block_d: int = 512,
           interpret: bool | None = None):
    """eq. 19-23: returns (dx (M, D), dgamma (1, D), dbeta (1, D))."""
    interpret = resolve_interpret(interpret)
    m, d = g.shape
    sp = _specs(m, d, block_m, block_d)
    gamma = gamma.reshape(1, d)
    vec = jax.ShapeDtypeStruct((1, d), jnp.float32)
    s_n, s_m, s_mn, s_g = pl.pallas_call(
        functools.partial(_bn_bwd_sums_kernel, m_rows=m, block_m=sp["bm"]),
        grid=sp["red_grid"], in_specs=[sp["red_tile"]] * 2
        + [sp["red_vec"]] * 3,
        out_specs=[sp["red_vec"]] * 4, out_shape=[vec] * 4,
        interpret=interpret)(g, x, gamma, mu, sqrt_d)
    dx = pl.pallas_call(
        functools.partial(_bn_bwd_dx_kernel, m_rows=m), grid=sp["ew_grid"],
        in_specs=[sp["ew_tile"]] * 2 + [sp["ew_vec"]] * 6,
        out_specs=sp["ew_tile"],
        out_shape=jax.ShapeDtypeStruct((m, d), g.dtype),
        interpret=interpret)(g, x, gamma, mu, sqrt_d, s_n, s_m, s_mn)
    dgamma = s_mn / gamma.astype(jnp.float32)                     # eq. 21
    return dx, dgamma, s_g


# ---------------------------------------------------------------------------
# Kernel-contract declarations (repro.analysis.contracts). BN launches both
# at its own sites (tokenizer.bn under the dense conv stage) and inside the
# pipeline arms of linear_bn / the fused conv, always on fold_rows output —
# the builders therefore collapse the case's (t, m) into the row axis.
# ---------------------------------------------------------------------------

from repro.kernels import ref as _ref  # noqa: E402
from repro.kernels.contract import KernelContract, declare_contract  # noqa: E402

_BN_SERVES = (("bn", "pallas"), ("linear_bn", "pallas"),
              ("linear_bn", "pallas+spike_mm"), ("conv", "pallas"),
              ("conv", "pallas_packed"))


def _build_bn_fwd(case):
    f = jax.ShapeDtypeStruct
    rows = case.t * case.m
    args = (f((rows, case.k), case.dtype), f((case.k,), case.dtype),
            f((case.k,), case.dtype))
    return args, {}, {}


def _build_bn_bwd(case):
    f = jax.ShapeDtypeStruct
    rows = case.t * case.m
    args = (f((rows, case.k), case.dtype), f((rows, case.k), case.dtype),
            f((case.k,), case.dtype), f((1, case.k), jnp.float32),
            f((1, case.k), jnp.float32))
    return args, {}, {}


declare_contract(KernelContract(
    name="bn_fwd", fn=bn_fwd, build=_build_bn_fwd, ref=_ref.bn_fwd_ref,
    serves=_BN_SERVES))

declare_contract(KernelContract(
    name="bn_bwd", fn=bn_bwd, build=_build_bn_bwd, ref=_ref.bn_bwd_ref,
    serves=_BN_SERVES))
