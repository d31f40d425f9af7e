"""Training step assembly: microbatched gradient accumulation (scan, so the
per-microbatch reduce-scatter overlaps the next microbatch's compute under
XLA's latency-hiding scheduler), AdamW apply, metrics.

``make_train_step(cfg, ...)`` is the single train-step factory for every
family — LM/audio (``ArchConfig``) and the Spikingformer vision path
(``SpikingFormerConfig``) — and returns a pure function suitable both for
jit execution and for ``.lower().compile()`` in the multi-pod dry-run.
Mesh awareness lives in the model code (``shard`` constraints that no-op
without an ambient mesh) plus the optional ``mesh=`` kwarg, which adds the
input-batch constraints; callers run the step under ``jax.set_mesh``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.train.optimizer import OptimizerConfig, adamw_update


def _loss_fn_for(cfg: ArchConfig) -> Callable:
    if cfg.family == "audio":
        from repro.models.encdec import encdec_loss
        return encdec_loss
    from repro.models.lm import lm_loss
    return lm_loss


def _all_finite(loss, grads) -> jax.Array:
    """Scalar bool: loss and every inexact grad leaf are fully finite.
    Tree-reduced inside the jit, so the guard costs one fused reduction —
    no host sync, no extra launch."""
    finite = jnp.isfinite(loss).all()
    for leaf in jax.tree.leaves(grads):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
            finite = finite & jnp.isfinite(leaf).all()
    return finite


def _select_tree(finite, new, old):
    """``new`` where the step was finite, ``old`` (state unchanged)
    otherwise — the in-jit skip: same trace either way."""
    return jax.tree.map(lambda n, o: jnp.where(finite, n, o), new, old)


def make_train_step(cfg: Any, opt_cfg: OptimizerConfig,
                    microbatches: int = 1, *, mesh=None,
                    guard_nonfinite: bool = True) -> Callable:
    """The unified train-step factory.

    * LM/audio (``cfg.family`` in {"lm", "audio", ...}): returns
      ``train_step(params, opt_state, batch) -> (params, opt_state,
      metrics)``. ``batch`` leaves have leading dim (global_batch, ...);
      with microbatches > 1 they are split (microbatches, global_batch //
      microbatches, ...) and accumulated.
    * Spikingformer vision (``cfg.family == "vision"``): returns
      ``train_step(params, state, opt_state, images, labels) -> (params,
      state, opt_state, metrics)`` where ``state`` carries BN running
      statistics.

    ``mesh`` adds the input-batch sharding constraints on the vision path
    (batch over the ("pod", "data") axes; the LM path's inputs arrive
    pre-placed by ``place_batch``); activation/parameter placement is the
    model's ``shard`` constraints plus the shardings params were
    initialized into (see ``launch.train.build_state`` /
    ``build_spikingformer_state``).

    ``guard_nonfinite`` (default on) adds in-jit non-finite detection: when
    the loss or any gradient leaf is NaN/Inf, the parameter and optimizer
    updates are suppressed via a tree-wide ``where`` (state bit-identical
    to before the step) and ``metrics["nonfinite"]`` reports 1.0. The
    driver (``launch.train._drive``) budgets *consecutive* skipped steps
    and aborts past the budget — a single poisoned batch self-heals, a
    diverged run still dies loudly.
    """
    if getattr(cfg, "family", None) == "vision":
        return _make_vision_train_step(cfg, opt_cfg, microbatches, mesh,
                                       guard_nonfinite)
    loss_fn = _loss_fn_for(cfg)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, cfg)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                    *x.shape[1:]), batch)

            def acc_fn(carry, micro):
                g_acc, l_acc = carry
                (loss, _), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, micro, cfg)
                g_acc = jax.tree.map(jnp.add, g_acc, grads)
                return (g_acc, l_acc + loss), None

            g0 = jax.tree.map(jnp.zeros_like, params)
            (grads, loss_sum), _ = jax.lax.scan(acc_fn, (g0, 0.0), mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss_sum / microbatches
            metrics = {"loss": loss}
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = adamw_update(
                params, grads, opt_state, opt_cfg)
            metrics = {**metrics, **opt_metrics}
            if guard_nonfinite:
                finite = _all_finite(loss, grads)
                new_params = _select_tree(finite, new_params, params)
                new_opt = _select_tree(finite, new_opt, opt_state)
                metrics["nonfinite"] = 1.0 - finite.astype(jnp.float32)
        return new_params, new_opt, metrics

    return train_step


def _make_vision_train_step(cfg, opt_cfg: OptimizerConfig,
                            microbatches: int, mesh,
                            guard_nonfinite: bool = True) -> Callable:
    """Fused BPTT + AdamW step for the Spikingformer vision path.

    ``cfg`` is a :class:`repro.core.spikingformer.SpikingFormerConfig`; its
    ``policy`` field (an :class:`repro.core.policy.ExecutionPolicy`) selects
    the execution path per site, so the same train step runs the reference
    jnp scan on CPU and the fused SOMA/GRAD (+ packed spike-matmul /
    packed-attention) kernels on TPU, and its ``time_chunk`` field tiles
    the BPTT scan temporally. Returns the pure ``step(params, state,
    opt_state, images, labels) -> (params, state, opt_state, metrics)``
    (callers jit it; :func:`make_spikingformer_train_step` does so for the
    single-device path) where ``state`` carries BN running statistics.
    """
    from repro.core.spikingformer import spikingformer_grad_step

    if microbatches != 1:
        # Accumulating grads across microbatches would also have to merge
        # BN batch statistics; refuse rather than silently change the math.
        raise NotImplementedError(
            "microbatch accumulation is not supported on the vision path "
            "(BatchNorm statistics are per-global-batch); use time_chunk "
            "for activation-memory relief instead")

    batch_axes_ = None
    if mesh is not None:
        from repro.launch.mesh import batch_axes
        batch_axes_ = batch_axes(mesh) or None

    def train_step(params, state, opt_state, images, labels):
        if batch_axes_ is not None:
            from jax.sharding import PartitionSpec as P
            # images: (B, H, W, C) static or (T, B, H, W, C) temporal
            lead = (None,) if images.ndim == 5 else ()
            img_spec = P(*lead, batch_axes_,
                         *([None] * (images.ndim - len(lead) - 1)))
            images = jax.lax.with_sharding_constraint(images, img_spec)
            labels = jax.lax.with_sharding_constraint(labels, P(batch_axes_))
        grads, new_state, metrics = spikingformer_grad_step(
            params, state, images, labels, cfg)
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = adamw_update(
                params, grads, opt_state, opt_cfg)
            metrics = {**metrics, **opt_metrics}
            if guard_nonfinite:
                finite = _all_finite(metrics["loss"], grads)
                new_params = _select_tree(finite, new_params, params)
                # BN running statistics ride the forward pass, so a
                # poisoned batch contaminates them too — roll them back
                # with the rest.
                new_state = _select_tree(finite, new_state, state)
                new_opt = _select_tree(finite, new_opt, opt_state)
                metrics["nonfinite"] = 1.0 - finite.astype(jnp.float32)
        return new_params, new_state, new_opt, metrics

    return train_step


def make_spikingformer_train_step(cfg, opt_cfg: OptimizerConfig) -> Callable:
    """Back-compat wrapper: the unified factory at mesh=None, jitted (the
    historical signature returned a jitted step)."""
    return jax.jit(make_train_step(cfg, opt_cfg))


def make_eval_step(cfg: ArchConfig) -> Callable:
    loss_fn = _loss_fn_for(cfg)

    def eval_step(params, batch):
        loss, metrics = loss_fn(params, batch, cfg)
        return metrics

    return eval_step
