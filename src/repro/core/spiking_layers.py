"""Spiking Transformer building blocks (Spikingformer [17], E2ATST Fig. 1-2).

Conventions
-----------
* Activations carry a leading time axis: ``x: (T, B, N, D)``. Matrix ops fold
  (T, B, N) into the paper's sequence length S = BS x T x P^2 (Table III).
* Every layer is a pair of pure functions ``init_*(key, ...) -> params`` and
  ``*_apply(params, state, x, ...) -> (y, new_state)``; ``state`` holds BN
  running statistics only.
* ``Conv1D == MM`` (paper §III-A): the Q/K/V/Z/A/B "Conv1DBN" layers are plain
  linear transforms followed by BatchNorm.
* Execution dispatches through the :mod:`repro.core.policy` kernel registry:
  each ``*_apply`` resolves its implementation from an
  :class:`~repro.core.policy.ExecutionPolicy` and a ``site`` name
  (``"pssa.qkv"``, ``"smlp.a"``, ``"attn_qk"``, ...) instead of branching on
  the PR 1 ``backend``/``spike_mm`` booleans. The old kwargs still work as
  deprecation shims.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core.backend import fold_rows, fold_time_major
from repro.core.lif import LIFConfig, lif_scan
from repro.core.policy import (ExecutionPolicy, FUSED_EPILOGUE_IMPLS,
                               apply_legacy_exec_flags, dispatch_kernel,
                               dispatch_site, fused_epilogue_fallback,
                               get_kernel, policy_from_flags, register_kernel,
                               runtime_fallback)
from repro.models.common import BATCH, MODEL, shard
from repro.tune.table import lookup as tuned_lookup

Params = dict[str, Any]
State = dict[str, Any]

#: Activation partition specs for the block-internal constraint points
#: (``shard`` no-ops without an ambient mesh, so the same code runs in
#: single-device tests and under the launch mesh). Batch over ("pod",
#: "data"); Q/K/V, attention-head and MLP-hidden features over "model"; the
#: residual stream keeps features replicated. See docs/SHARDING.md.
ACT_SPECS: dict[str, P] = {
    "block.residual": P(None, BATCH, None, None),     # (T,B,N,D)
    "pssa.qkv": P(None, BATCH, None, MODEL),          # (T,B,N,D)
    "attn.scores": P(None, BATCH, MODEL, None, None),  # (T,B,h,N,M)
    "pssa.out": P(None, BATCH, None, MODEL),          # (T,B,N,D) merged heads
    "smlp.hidden": P(None, BATCH, None, MODEL),       # (T,B,N,F)
}


def _legacy_policy(policy: ExecutionPolicy | None, backend: str | None,
                   spike_mm: bool | None, interpret: bool | None,
                   what: str) -> ExecutionPolicy:
    """Fold deprecated per-call flags into a policy (warning when used)."""
    if backend is not None or spike_mm is not None or interpret is not None:
        from repro.core.policy import warn_deprecated_flags
        # user -> bn_apply/linear_bn_apply -> here: 3 frames up.
        warn_deprecated_flags(what, stacklevel=3)
        return policy_from_flags(backend, spike_mm, interpret,
                                 base=policy or ExecutionPolicy())
    return policy if policy is not None else ExecutionPolicy()


# ---------------------------------------------------------------------------
# BatchNorm (paper eq. 13-18 forward; BP handled by autodiff == eq. 19-23)
# ---------------------------------------------------------------------------

def init_bn(dim: int, dtype=jnp.float32) -> tuple[Params, State]:
    params = {"gamma": jnp.ones((dim,), dtype), "beta": jnp.zeros((dim,), dtype)}
    state = {"mean": jnp.zeros((dim,), jnp.float32),
             "var": jnp.ones((dim,), jnp.float32)}
    return params, state


@register_kernel("bn", "jnp")
def _bn_jnp(params, state, x, train, momentum, eps, policy, site):
    """Pure-jnp BatchNorm, the paper's E[x^2] - mu^2 formulation (eq. 13-18);
    statistics in fp32. Also the eval path for every implementation."""
    axes = tuple(range(x.ndim - 1))
    if train:
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=axes)
        ex2 = jnp.mean(jnp.square(xf), axis=axes)            # eq. 14
        var = jnp.maximum(ex2 - jnp.square(mu), 0.0)          # eq. 15
        new_state = {"mean": momentum * state["mean"] + (1 - momentum) * mu,
                     "var": momentum * state["var"] + (1 - momentum) * var}
    else:
        mu, var = state["mean"], state["var"]
        new_state = state
    sqrt_d = jnp.sqrt(var + eps)                               # eq. 16
    y = (x - mu.astype(x.dtype)) / sqrt_d.astype(x.dtype)      # eq. 17
    y = params["gamma"] * y + params["beta"]                   # eq. 18
    return y, new_state


@register_kernel("bn", "pallas")
def _bn_pallas(params, state, x, train, momentum, eps, policy, site):
    """Fused BN FP/BP kernel pair (``ops.bn_train_op``, eq. 13-23): one VMEM
    visit computes stats and normalizes; the batch mu/var the kernel already
    computed are blended into the running stats (no second pass over x).
    Eval always uses the running-stat jnp path."""
    if not train:
        return _bn_jnp(params, state, x, train, momentum, eps, policy, site)
    from repro.kernels import ops

    x2, shape = fold_rows(x)
    y, mu, var = ops.bn_train_op(x2, params["gamma"], params["beta"],
                                 eps, policy.interpret)
    var = jnp.maximum(var, 0.0)   # sqrt_d^2 - eps can round below zero
    new_state = {"mean": momentum * state["mean"] + (1 - momentum) * mu,
                 "var": momentum * state["var"] + (1 - momentum) * var}
    return y.reshape(shape), new_state


def bn_apply(params: Params, state: State, x: jax.Array, *, train: bool,
             momentum: float = 0.9, eps: float = 1e-5,
             policy: ExecutionPolicy | None = None, site: str = "bn",
             backend: str | None = None, interpret: bool | None = None):
    """BatchNorm over all axes but the last (features d).

    The implementation is resolved through the kernel registry from
    ``policy`` and ``site`` (``backend=``/``interpret=`` are deprecated
    shims). Statistics are fp32 under every implementation.
    """
    policy = _legacy_policy(policy, backend, None, interpret,
                            "bn_apply(backend=/interpret=)")
    impl = policy.resolve(site, "bn")
    return dispatch_kernel(site, "bn", impl, params, state, x, train,
                           momentum, eps, policy, site)


# ---------------------------------------------------------------------------
# Linear (+ BN) layers
# ---------------------------------------------------------------------------

def init_linear(key, d_in: int, d_out: int, dtype=jnp.float32,
                scale: float | None = None) -> Params:
    scale = scale if scale is not None else d_in ** -0.5
    w = jax.random.normal(key, (d_in, d_out), dtype) * scale
    return {"w": w}


def linear_apply(params: Params, x: jax.Array) -> jax.Array:
    return x @ params["w"].astype(x.dtype)


def init_linear_bn(key, d_in: int, d_out: int, dtype=jnp.float32):
    params = init_linear(key, d_in, d_out, dtype)
    bn_p, bn_s = init_bn(d_out, dtype)
    return {"linear": params, "bn": bn_p}, {"bn": bn_s}


@register_kernel("linear_bn", "jnp")
def _linear_bn_jnp(params, state, x, train, policy, site):
    """Dense matmul + jnp BatchNorm."""
    y = linear_apply(params["linear"], x)
    y, bn_s = _bn_jnp(params["bn"], state["bn"], y, train, 0.9, 1e-5,
                      policy, site)
    return y, {"bn": bn_s}


@register_kernel("linear_bn", "pallas")
def _linear_bn_pallas(params, state, x, train, policy, site):
    """Dense matmul + fused-Pallas BatchNorm."""
    y = linear_apply(params["linear"], x)
    y, bn_s = _bn_pallas(params["bn"], state["bn"], y, train, 0.9, 1e-5,
                         policy, site)
    return y, {"bn": bn_s}


@register_kernel("linear_bn", "pallas+spike_mm")
def _linear_bn_spike_mm(params, state, x, train, policy, site):
    """Bit-packed spike matmul + fused-Pallas BatchNorm.

    Inputs must be {0,1} spikes — true at every Conv1DBN site in PSSA/SMLP,
    which all consume LIF outputs. The packing constraint (contraction dim
    % 8 == 0) is resolved per site at policy-validation time
    (:func:`repro.core.policy.plan_sites`); if a direct call still violates
    it, the dense path is used and the fallback is *logged*, not silent.
    """
    w = params["linear"]["w"]
    if x.shape[-1] % 8 == 0:
        from repro.kernels import ops

        x2, shape = fold_rows(x)
        tb = tuned_lookup(site, "linear_bn", "pallas+spike_mm",
                          (x2.shape[0], x2.shape[1], w.shape[-1]), True)
        y = ops.spike_matmul_train_op(x2, w.astype(x.dtype), policy.interpret,
                                      tb.mm_blocks() if tb else None)
        y = y.reshape(*shape[:-1], w.shape[-1])
    else:
        runtime_fallback(site, "pallas+spike_mm",
                         f"contraction dim {x.shape[-1]} % 8 != 0 -> dense")
        y = linear_apply(params["linear"], x)
    y, bn_s = _bn_pallas(params["bn"], state["bn"], y, train, 0.9, 1e-5,
                         policy, site)
    return y, {"bn": bn_s}


def train_arm_vmem_demotion(t: int, m: int, c: int, k: int, packed: bool,
                            policy: ExecutionPolicy) -> str | None:
    """Why the train-mode megakernel cannot hold a ``(T, M, C) @ (C, K)``
    site on the compiling backend, or None where it fits. Its
    BN-statistics constraint pins all T*M rows to one program, so at large
    M its tiles outgrow VMEM where the M-tiled pipeline still fits.
    ``packed`` must be the arm the caller will actually run. Interpret
    mode (every CPU/CI run) has no such limit. The execution plan
    (``SpikingFormerConfig.execution_plan(batch=...)``) and the per-call
    guard both decide through here, so they cannot disagree."""
    from repro.core.backend import resolve_interpret
    from repro.kernels import neuron_layer

    if resolve_interpret(policy.interpret):
        return None
    est = neuron_layer.train_arm_vmem_bytes(t, m, c, k, packed=packed)
    budget = neuron_layer.TRAIN_ARM_VMEM_BUDGET
    if est <= budget:
        return None
    return (f"train-arm VMEM estimate {est / 2**20:.1f} MiB > "
            f"{budget / 2**20:.1f} MiB (all T*M rows per program)")


def _train_arm_exceeds_vmem(x, k_out, packed, policy, site) -> bool:
    """Per-call capacity guard (see :func:`train_arm_vmem_demotion`); the
    demotion is logged at INFO — a planned capacity decision, which the
    plan reports too when it is given the batch."""
    t, m, c = x.shape[0], math.prod(x.shape[1:-1]), x.shape[-1]
    reason = train_arm_vmem_demotion(t, m, c, k_out, packed, policy)
    if reason is None:
        return False
    runtime_fallback(site, "fused_epilogue", reason + " -> pipeline",
                     expected=True)
    return True


def _tuned_prefers_pipeline(site, op, impl, shape, packed, policy) -> bool:
    """True when the active tuned-block table *measured* the M-tiled
    pipeline arm as faster than the single-launch megakernel at this site.
    An exact site-level policy override pinning a fused impl wins over the
    table (explicit policy beats measurement); the demotion is logged as an
    expected, planned decision — like the VMEM capacity guard."""
    tb = tuned_lookup(site, op, impl, shape, packed)
    if tb is None or tb.arm != "pipeline":
        return False
    if dict(policy.overrides).get(site) in FUSED_EPILOGUE_IMPLS:
        return False
    runtime_fallback(site, impl,
                     "tuned table prefers the pipeline arm -> "
                     f"{fused_epilogue_fallback(op, impl)}", expected=True)
    return True


def _neuron_layer_site(x3, w_mat, bn_p, bn_s, lif_cfg, train, packed,
                       interpret, tuned=None):
    """Shared fused-epilogue core: ``x3 (T, M, C) @ w_mat (C, K)`` + BN +
    SOMA in ONE Pallas launch (``kernels/neuron_layer.py``). Train mode
    computes the batch statistics in-kernel and blends the running stats
    (momentum 0.9, like ``_bn_pallas``); eval folds BN into the weights and
    a bias RTFormer-style. ``tuned`` is the site's
    :class:`repro.tune.table.TunedBlocks` entry (or None for kernel
    defaults). Returns ``(spikes (T, M, K), new_bn_state)``."""
    from repro.kernels import conv_spike, ops  # deferred: jnp path stays light

    lif = lif_cfg
    if train:
        spikes, mu, var = ops.neuron_layer_train_op(
            x3, w_mat.astype(x3.dtype), bn_p["gamma"], bn_p["beta"],
            lif.alpha, lif.th_fire, lif.th_lo, lif.th_hi, lif.grad_scale,
            1e-5, packed, interpret,
            tuned.train_blocks() if tuned is not None else None)
        new_bn = {"mean": 0.9 * bn_s["mean"] + 0.1 * mu,
                  "var": 0.9 * bn_s["var"] + 0.1 * var}
        return spikes, new_bn
    w_fold, bias = conv_spike.fold_bn(w_mat, bn_p["gamma"], bn_p["beta"],
                                      bn_s["mean"], bn_s["var"])
    # The tuned entry is measured on the train arm; its (block_k, block_c)
    # transfer to eval (same K/C axes), block_m stays a kernel default
    # unless the entry carries one.
    eval_blocks = ((tuned.block_m, tuned.block_k, tuned.block_c)
                   if tuned is not None else None)
    spikes = ops.neuron_layer_eval_op(
        x3, w_fold.astype(x3.dtype), bias, lif.alpha, lif.th_fire, lif.th_lo,
        lif.th_hi, lif.grad_scale, packed, interpret, eval_blocks)
    return spikes, bn_s


@register_kernel("linear_bn", "fused_epilogue")
def _linear_bn_fused_epilogue(params, state, x, lif_cfg, train, policy, site):
    """Single-launch neuron layer: bit-packed (or dense) spike matmul +
    BatchNorm + SOMA in ONE Pallas kernel — the (T, M, K) pre-activation
    never exists in HBM, and the backward replays it through the GRAD
    kernel instead of storing per-step residuals.

    Extended signature (takes the LIF config of the SN it absorbs); only
    dispatched via :func:`linear_bn_lif_apply` at trailing-LIF sites.
    Inputs must be {0,1} spikes — true at every such Conv1DBN site, which
    all consume LIF outputs. A ragged contraction (% 8 != 0) keeps the
    single launch on the dense arm, logged, never silent.
    """
    x3, shape = fold_time_major(x)
    packed = x3.shape[-1] % 8 == 0
    if not packed:
        runtime_fallback(site, "fused_epilogue",
                         f"contraction dim {x3.shape[-1]} % 8 != 0 -> "
                         f"dense arm (still fused)")
    w = params["linear"]["w"]
    tb = tuned_lookup(site, "linear_bn", "fused_epilogue",
                      x3.shape + (w.shape[-1],), packed)
    spikes, bn_s = _neuron_layer_site(x3, w, params["bn"], state["bn"],
                                      lif_cfg, train, packed,
                                      policy.interpret, tb)
    return spikes.reshape(*shape[:-1], w.shape[-1]), {"bn": bn_s}


def linear_bn_apply(params: Params, state: State, x: jax.Array, *,
                    train: bool, policy: ExecutionPolicy | None = None,
                    site: str = "linear_bn", backend: str | None = None,
                    spike_mm: bool | None = None,
                    interpret: bool | None = None):
    """The paper's Conv1DBN: spike (or real) input -> MM -> BN.

    Registered implementations: ``"jnp"`` (dense + jnp BN), ``"pallas"``
    (dense + fused BN), ``"pallas+spike_mm"`` (bit-packed spike matmul +
    fused BN). ``backend=``/``spike_mm=``/``interpret=`` are deprecated
    shims over ``policy``. A ``"fused_epilogue"`` resolution cannot be
    honoured here — this entry point returns the pre-activation and there
    is no SN to fuse — so it demotes (logged as the plan predicted) to its
    pipeline fallback; the fused path lives in
    :func:`linear_bn_lif_apply`.
    """
    policy = _legacy_policy(policy, backend, spike_mm, interpret,
                            "linear_bn_apply(backend=/spike_mm=/interpret=)")
    impl = policy.resolve(site, "linear_bn")
    if impl in FUSED_EPILOGUE_IMPLS:
        fb = fused_epilogue_fallback("linear_bn", impl)
        runtime_fallback(site, impl, f"no trailing LIF at this site -> {fb}",
                         expected=True)
        impl = fb
    return dispatch_kernel(site, "linear_bn", impl, params, state, x, train,
                           policy, site)


def linear_bn_lif_apply(params: Params, state: State, x: jax.Array,
                        lif_cfg: LIFConfig, *, train: bool,
                        policy: ExecutionPolicy | None = None,
                        site: str = "linear_bn", lif_site: str = "lif",
                        act_spec: P | None = None):
    """The Conv1DBN -> SN pair (the model's "neuron layer"): matmul + BN at
    ``site`` followed by the LIF scan at ``lif_site``.

    When the policy resolves ``site`` to a fused-epilogue implementation,
    the whole pair runs as ONE Pallas launch (matmul + BN + SOMA megakernel,
    no HBM pre-activation) and ``lif_site`` never dispatches — 3 launches
    collapse to 1. Otherwise this is exactly the previous pipeline:
    ``linear_bn`` dispatch, optional sharding constraint, ``lif_scan``.
    ``act_spec`` (a PartitionSpec) is applied to the pre-activation on the
    pipeline path and to the spikes on the fused path — same placement,
    the tensor it pins just no longer exists in the fused case.

    ``lif_cfg.time_chunk`` note: the fused op runs the full T single-shot
    — its replay-based backward already stores no per-step residuals, which
    is the memory profile ``time_chunk`` exists to provide — so outputs and
    gradients are exactly the single-shot values regardless of the setting
    (the non-absorbed LIF sites still tile).
    """
    policy = policy if policy is not None else ExecutionPolicy()
    impl = policy.resolve(site, "linear_bn")
    if impl in FUSED_EPILOGUE_IMPLS and train and \
            _train_arm_exceeds_vmem(x, params["linear"]["w"].shape[-1],
                                    x.shape[-1] % 8 == 0, policy, site):
        impl = fused_epilogue_fallback("linear_bn", impl)
    if impl in FUSED_EPILOGUE_IMPLS and train:
        x3shape = (x.shape[0], math.prod(x.shape[1:-1]), x.shape[-1],
                   params["linear"]["w"].shape[-1])
        if _tuned_prefers_pipeline(site, "linear_bn", impl, x3shape,
                                   x.shape[-1] % 8 == 0, policy):
            impl = fused_epilogue_fallback("linear_bn", impl)
    def _pipeline(pipe_impl):
        y, st = dispatch_kernel(site, "linear_bn", pipe_impl, params, state,
                                x, train, policy, site)
        if act_spec is not None:
            y = shard(y, *act_spec)
        return lif_scan(y, lif_cfg, site=lif_site), st

    if impl in FUSED_EPILOGUE_IMPLS:
        # The megakernel's circuit-breaker fallback is the full reference
        # *pipeline* (jnp linear_bn + lif_scan), not a same-signature impl
        # swap — the fused impl absorbed the trailing LIF.
        def _fused():
            spikes, st = get_kernel("linear_bn", impl)(
                params, state, x, lif_cfg, train, policy, site)
            if act_spec is not None:
                spikes = shard(spikes, *act_spec)
            return spikes, st

        return dispatch_site(site, "linear_bn", impl, _fused,
                             fallback_impl="jnp",
                             fallback_invoke=lambda: _pipeline("jnp"))
    return _pipeline(impl)


# ---------------------------------------------------------------------------
# Attention einsums (the PSSA (QK^T)V path), registry ops attn_qk / attn_av
# ---------------------------------------------------------------------------

@register_kernel("attn_qk", "jnp")
def _attn_qk_jnp(q, k, policy, site):
    """Spike-count scores: (T,B,h,N,dh) x (T,B,h,M,dh) -> (T,B,h,N,M)."""
    return jnp.einsum("tbhnd,tbhmd->tbhnm", q, k)


@register_kernel("attn_qk", "pallas_packed")
def _attn_qk_packed(q, k, policy, site):
    """Packed Q K^T: Q rides HBM->VMEM at 1 bit/element.

    Both operands are {0,1} LIF outputs; fold (T,B,h) to a batch axis and
    run the batched bit-packed kernel with K^T as the dense-side operand.
    The packing constraint is the head dim (contraction) % 8.
    """
    t, b, h, n, dh = q.shape
    m = k.shape[3]
    if dh % 8 != 0:
        # Architectural (d_model / n_heads), so the plan marks it expected.
        runtime_fallback(site, "pallas_packed",
                         f"head dim {dh} % 8 != 0 -> jnp einsum",
                         expected=True)
        return _attn_qk_jnp(q, k, policy, site)
    from repro.kernels import ops

    tb = tuned_lookup(site, "attn_qk", "pallas_packed",
                      (t * b * h, n, dh, m), True)
    out = ops.spike_bmm_train_op(q.reshape(t * b * h, n, dh),
                                 k.reshape(t * b * h, m, dh).transpose(0, 2, 1),
                                 policy.interpret,
                                 tb.mm_blocks() if tb else None)
    return out.reshape(t, b, h, n, m)


@register_kernel("attn_av", "jnp")
def _attn_av_jnp(attn, v, policy, site):
    """(T,B,h,N,M) scores x (T,B,h,M,dh) spike values -> (T,B,h,N,dh)."""
    return jnp.einsum("tbhnm,tbhmd->tbhnd", attn, v)


@register_kernel("attn_av", "pallas_packed")
def _attn_av_packed(attn, v, policy, site):
    """Packed (attn) V via the transpose trick.

    The spike operand here is V, which sits on the *right* of the matmul;
    the kernel packs its left operand, so compute out^T = V^T attn^T with
    V^T (dh, M) as the packed {0,1} side. The packing constraint is the
    token count M (contraction) % 8.
    """
    t, b, h, n, m = attn.shape
    dh = v.shape[-1]
    if m % 8 != 0:
        # Architectural (patch_grid^2), so the plan marks it expected.
        runtime_fallback(site, "pallas_packed",
                         f"token count {m} % 8 != 0 -> jnp einsum",
                         expected=True)
        return _attn_av_jnp(attn, v, policy, site)
    from repro.kernels import ops

    vt = v.reshape(t * b * h, m, dh).transpose(0, 2, 1)       # (G, dh, M) {0,1}
    at = attn.reshape(t * b * h, n, m).transpose(0, 2, 1)     # (G, M, N)
    tb = tuned_lookup(site, "attn_av", "pallas_packed",
                      (t * b * h, dh, m, n), True)
    out_t = ops.spike_bmm_train_op(vt, at, policy.interpret,
                                   tb.mm_blocks() if tb else None)  # (G,dh,N)
    return out_t.transpose(0, 2, 1).reshape(t, b, h, n, dh)


# ---------------------------------------------------------------------------
# PSSA: Pre-activation Spiking Self-Attention (eq. 8-10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PSSAConfig:
    d_model: int
    n_heads: int
    lif: LIFConfig = LIFConfig()
    # QK^T V scaling factor s (Spikformer uses 0.125)
    scale: float = 0.125
    # True: (Q K^T) V as in the paper's energy model (2 S^2 d_h term).
    # False: Q (K^T V) — algebraically identical (no softmax!), O(S d^2);
    #        this is the beyond-paper TPU optimization (see DESIGN.md §3).
    qk_first: bool = True
    policy: ExecutionPolicy = ExecutionPolicy()
    # Deprecated PR 1 spellings, folded into ``policy`` with a warning:
    backend: dataclasses.InitVar[str | None] = None
    spike_mm: dataclasses.InitVar[bool | None] = None
    interpret: dataclasses.InitVar[bool | None] = None

    def __post_init__(self, backend, spike_mm, interpret):
        apply_legacy_exec_flags(self, backend, spike_mm, interpret)

    @property
    def lif_cfg(self) -> LIFConfig:
        """The LIF config with this layer's policy injected (single switch)."""
        return dataclasses.replace(self.lif, policy=self.policy)


def init_pssa(key, cfg: PSSAConfig, dtype=jnp.float32):
    kq, kk, kv, kz = jax.random.split(key, 4)
    d = cfg.d_model
    pq, sq = init_linear_bn(kq, d, d, dtype)
    pk, sk = init_linear_bn(kk, d, d, dtype)
    pv, sv = init_linear_bn(kv, d, d, dtype)
    pz, sz = init_linear_bn(kz, d, d, dtype)
    return ({"q": pq, "k": pk, "v": pv, "z": pz},
            {"q": sq, "k": sk, "v": sv, "z": sz})


def _split_heads(x: jax.Array, h: int) -> jax.Array:
    t, b, n, d = x.shape
    return x.reshape(t, b, n, h, d // h).transpose(0, 1, 3, 2, 4)  # (T,B,h,N,dh)


def _merge_heads(x: jax.Array) -> jax.Array:
    t, b, h, n, dh = x.shape
    return x.transpose(0, 1, 3, 2, 4).reshape(t, b, n, h * dh)


def pssa_apply(params: Params, state: State, x: jax.Array, cfg: PSSAConfig,
               *, train: bool):
    """x: (T,B,N,D) real-valued features -> (T,B,N,D); residual added by caller."""
    pol = cfg.policy
    xs = lif_scan(x, cfg.lif_cfg, site="pssa.lif")              # eq. 8  X' = SN(X)
    # eq. 9: each Conv1DBN -> SN pair is one "neuron layer" — under a
    # fused-epilogue policy the matmul+BN+SOMA run as a single launch.
    qs, s_q = linear_bn_lif_apply(params["q"], state["q"], xs, cfg.lif_cfg,
                                  train=train, policy=pol, site="pssa.qkv",
                                  lif_site="pssa.lif",
                                  act_spec=ACT_SPECS["pssa.qkv"])
    ks, s_k = linear_bn_lif_apply(params["k"], state["k"], xs, cfg.lif_cfg,
                                  train=train, policy=pol, site="pssa.qkv",
                                  lif_site="pssa.lif",
                                  act_spec=ACT_SPECS["pssa.qkv"])
    vs, s_v = linear_bn_lif_apply(params["v"], state["v"], xs, cfg.lif_cfg,
                                  train=train, policy=pol, site="pssa.qkv",
                                  lif_site="pssa.lif",
                                  act_spec=ACT_SPECS["pssa.qkv"])

    qh, kh, vh = (_split_heads(a, cfg.n_heads) for a in (qs, ks, vs))
    if cfg.qk_first:
        attn = dispatch_kernel("attn_qk", "attn_qk",
                               pol.resolve("attn_qk", "attn_qk"),
                               qh, kh, pol, "attn_qk")           # spike counts
        attn = shard(attn, *ACT_SPECS["attn.scores"])
        out = dispatch_kernel("attn_av", "attn_av",
                              pol.resolve("attn_av", "attn_av"),
                              attn, vh, pol, "attn_av")
    else:  # exact reassociation (no softmax): K^T V first — kv is dense
        kv = jnp.einsum("tbhmd,tbhme->tbhde", kh, vh)
        out = jnp.einsum("tbhnd,tbhde->tbhne", qh, kv)
    out = shard(_merge_heads(out), *ACT_SPECS["pssa.out"]) * cfg.scale  # eq. 10
    out_s = lif_scan(out, cfg.lif_cfg, site="pssa.lif")          # SN(...)
    z, s_z = linear_bn_apply(params["z"], state["z"], out_s, train=train,
                             policy=pol, site="pssa.proj")
    return z, {"q": s_q, "k": s_k, "v": s_v, "z": s_z}


# ---------------------------------------------------------------------------
# Spiking MLP (Fig. 2: Linear A -> BN -> SN -> Linear B -> BN)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SMLPConfig:
    d_model: int
    d_ff: int
    lif: LIFConfig = LIFConfig()
    policy: ExecutionPolicy = ExecutionPolicy()
    backend: dataclasses.InitVar[str | None] = None
    spike_mm: dataclasses.InitVar[bool | None] = None
    interpret: dataclasses.InitVar[bool | None] = None

    def __post_init__(self, backend, spike_mm, interpret):
        apply_legacy_exec_flags(self, backend, spike_mm, interpret)

    @property
    def lif_cfg(self) -> LIFConfig:
        return dataclasses.replace(self.lif, policy=self.policy)


def init_smlp(key, cfg: SMLPConfig, dtype=jnp.float32):
    ka, kb = jax.random.split(key)
    pa, sa = init_linear_bn(ka, cfg.d_model, cfg.d_ff, dtype)
    pb, sb = init_linear_bn(kb, cfg.d_ff, cfg.d_model, dtype)
    return {"a": pa, "b": pb}, {"a": sa, "b": sb}


def smlp_apply(params: Params, state: State, x: jax.Array, cfg: SMLPConfig,
               *, train: bool):
    pol = cfg.policy
    xs = lif_scan(x, cfg.lif_cfg, site="smlp.lif")   # pre-activation SN
    hs, s_a = linear_bn_lif_apply(params["a"], state["a"], xs, cfg.lif_cfg,
                                  train=train, policy=pol, site="smlp.a",
                                  lif_site="smlp.lif",
                                  act_spec=ACT_SPECS["smlp.hidden"])
    y, s_b = linear_bn_apply(params["b"], state["b"], hs, train=train,
                             policy=pol, site="smlp.b")
    return y, {"a": s_a, "b": s_b}


# ---------------------------------------------------------------------------
# Spiking Transformer block (eq. 5-6, MS residual adds)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockConfig:
    d_model: int
    n_heads: int
    d_ff: int
    lif: LIFConfig = LIFConfig()
    qk_first: bool = True
    attn_scale: float = 0.125
    policy: ExecutionPolicy = ExecutionPolicy()   # one switch for the block
    backend: dataclasses.InitVar[str | None] = None
    spike_mm: dataclasses.InitVar[bool | None] = None
    interpret: dataclasses.InitVar[bool | None] = None

    def __post_init__(self, backend, spike_mm, interpret):
        apply_legacy_exec_flags(self, backend, spike_mm, interpret)

    @property
    def pssa(self) -> PSSAConfig:
        return PSSAConfig(self.d_model, self.n_heads, self.lif,
                          self.attn_scale, self.qk_first, policy=self.policy)

    @property
    def smlp(self) -> SMLPConfig:
        return SMLPConfig(self.d_model, self.d_ff, self.lif,
                          policy=self.policy)


def init_block(key, cfg: BlockConfig, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    p_attn, s_attn = init_pssa(k1, cfg.pssa, dtype)
    p_mlp, s_mlp = init_smlp(k2, cfg.smlp, dtype)
    return {"pssa": p_attn, "smlp": p_mlp}, {"pssa": s_attn, "smlp": s_mlp}


def block_apply(params: Params, state: State, x: jax.Array, cfg: BlockConfig,
                *, train: bool):
    a, s_attn = pssa_apply(params["pssa"], state["pssa"], x, cfg.pssa, train=train)
    x = shard(x + a, *ACT_SPECS["block.residual"])   # eq. 5 (RES, MS Add)
    m, s_mlp = smlp_apply(params["smlp"], state["smlp"], x, cfg.smlp, train=train)
    x = shard(x + m, *ACT_SPECS["block.residual"])   # eq. 6 (RES)
    return x, {"pssa": s_attn, "smlp": s_mlp}
