"""QKFormer on the vision train path, against the plain reference
(``bench/configs/qkformer_reference.py``) at the smoke size: three stages at
widths 16 / 32 / 64, one block each, T=2, 32x32 images, seeded random
weights.

Tolerances. The program and the reference compute the same float32
operations in different orders (the program's BN statistics, fused
reshapes and the depth loop; the reference's scan over depth), so their
pre-activations differ by float32 rounding, ~1e-7 relative. A rounding
difference moves a spike only where a membrane potential sits within that
distance of a threshold, which at this size no seed here does, so the
readings agree far inside the limits below; the limits leave room for a
rare near-threshold potential, and the bfloat16 reference, whose rounding
(~4e-3) flips spikes through every layer, fails them.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.cells import BENCH
from bench.configs import qkformer_reference as ref
from repro.configs.qkformer import QKFORMER_PRESETS
from repro.configs.spikingformer import get_spikingformer_config
from repro.core.lif import LIFConfig
from repro.core.policy import named_policy, NAMED_POLICIES, site_tables
from repro.core.qkformer import (FLOAT_INPUT_SITES, qkformer_grad_step,
                                 qkta_mask)
from repro.train.loop import make_train_step
from repro.train.optimizer import OptimizerConfig, init_opt_state

SMOKE = dict(widths=[16, 32, 64], depths=[1, 1, 1], time_steps=2,
             image_size=32, num_classes=10)
BATCH, STEPS = 4, 3
#: |loss - reference| of the first step, nats: float32 rounding of a
#: 10-class cross-entropy is ~1e-7; 1e-4 leaves room for one spike moved
#: near a threshold.
LOSS_TOL = 1e-4
#: Per leaf, ||g - g_ref|| / ||g_ref||: float32 rounding through BN's
#: normalisation is ~1e-6 relative; 1e-3 allows one moved spike.
GRAD_TOL = 1e-3
#: Per leaf, ||p - p_ref|| / ||p_ref - p0|| of the parameters after 3 AdamW
#: steps, over the change the reference made: Adam divides by sqrt(v), so
#: a gradient entry near zero can move its update by O(lr) on rounding
#: alone; 1e-2 of the change.
PARAM_TOL = 1e-2


def _model() -> dict:
    with open(BENCH / "configs" / "qkformer-10-768.json") as f:
        model = json.load(f)
    model["model"].update(SMOKE)
    return model


MODEL = _model()
CFG = get_spikingformer_config("qkformer-smoke", policy=named_policy("jnp"))
OPT = dataclasses.replace(OptimizerConfig(), **{
    k: v for k, v in MODEL["optimizer"].items() if k != "decay"})


def _inputs(seed=0):
    k_img, k_lab = jax.random.split(jax.random.PRNGKey(seed))
    images = jax.random.normal(k_img, (BATCH, 32, 32, 3))
    images = images + 2.0 * (jnp.arange(32) < 16)[None, :, None, None]
    labels = jax.random.randint(k_lab, (BATCH,), 0, 10)
    return images, labels


def _start(seed=0):
    """Seeded weights, every BN shift drawn from N(0, 0.5^2). With shifts
    at 0 the last stage's 4 tokens of 8 channels a head never bring SN_attn
    to its threshold, and its Q, K, V and projection get no gradient."""
    params, state = CFG.init(jax.random.PRNGKey(seed))
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    return jax.tree_util.tree_unflatten(tree, [
        a + 0.5 * jax.random.normal(k, a.shape)
        if jax.tree_util.keystr(path).endswith("['beta']") else a
        for (path, a), k in zip(flat, keys)]), state


def _rel(got, want, scale=None):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(
        want if scale is None else scale)


def _leaf_gaps(got, want, scale=None):
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    scales = jax.tree.leaves(scale) if scale is not None else [None] * len(
        flat)
    return {jax.tree_util.keystr(p): _rel(g, w, s) for (p, w), g, s in
            zip(flat, jax.tree.leaves(got), scales)}


@pytest.fixture(scope="module")
def program():
    """First-step loss and gradients, and the parameters after STEPS
    AdamW steps, of the program's own train step."""
    params, state = _start()
    images, labels = _inputs()
    grads, _, metrics = jax.jit(
        lambda p, s: qkformer_grad_step(p, s, images, labels, CFG))(
        params, state)
    step = jax.jit(make_train_step(CFG, OPT))
    p, s, o = params, state, init_opt_state(params)
    for i in range(STEPS):
        p, s, o, _ = step(p, s, o, *_inputs(i))
    return float(metrics["loss"]), grads, p


def _reference(dtype):
    params, state = _start()
    images, labels = _inputs()
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa
    (loss, _), grads = jax.jit(lambda p, s: jax.value_and_grad(
        ref.loss_of, has_aux=True)(p, s, images.astype(dtype), labels,
                                   MODEL))(cast(params), cast(state))
    step = jax.jit(ref.make_step(MODEL, dtype))
    zeros = jax.tree.map(jnp.zeros_like, params)
    p, s = params, state
    o = {"m": zeros, "v": zeros, "step": jnp.zeros((), jnp.int32)}
    for i in range(STEPS):
        p, s, o, _ = step(p, s, o, *_inputs(i))
    return float(loss), grads, p


@pytest.fixture(scope="module")
def reference():
    return _reference(jnp.float32)


@pytest.fixture(scope="module")
def control():
    return _reference(jnp.bfloat16)


def _gaps(got, want):
    start, _ = _start()
    change = jax.tree.map(jnp.subtract, want[2], start)
    return {"loss": abs(got[0] - want[0]),
            "grad": max(_leaf_gaps(got[1], want[1]).values()),
            "param": max(_leaf_gaps(got[2], want[2], change).values())}


def test_the_program_matches_the_reference(program, reference):
    gaps = _gaps(program, reference)
    assert gaps["loss"] <= LOSS_TOL, gaps
    assert gaps["grad"] <= GRAD_TOL, gaps
    assert gaps["param"] <= PARAM_TOL, gaps


def test_the_bfloat16_reference_fails_the_tolerances(program, control):
    gaps = _gaps(program, control)
    assert (gaps["loss"] > LOSS_TOL or gaps["grad"] > GRAD_TOL
            or gaps["param"] > PARAM_TOL), gaps


def test_the_token_mask_fires_on_each_heads_query_count():
    """Hand-set spikes, T=3, one token, two heads of two channels: the
    mask is LIF(sum of the head's Q channels) at threshold 0.5, and the
    output is the mask times K on the head's channels."""
    q = jnp.array([[1, 0, 0, 0], [0, 0, 1, 1], [0, 0, 0, 0]],
                  jnp.float32).reshape(3, 1, 1, 4)
    k = jnp.array([[1, 1, 1, 0], [0, 1, 1, 1], [1, 1, 1, 1]],
                  jnp.float32).reshape(3, 1, 1, 4)
    # head sums per step: (1, 0), (0, 2), (0, 0); U_t = 0.5 U (1 - S) + X
    mask = np.array([[1, 0], [0, 1], [0, 0]], np.float32)
    out = qkta_mask(q, k, 2, LIFConfig(th_fire=0.5, th_lo=-0.5, th_hi=1.5))
    want = (np.repeat(mask, 2, axis=1) * np.asarray(k).reshape(3, 4))
    np.testing.assert_array_equal(np.asarray(out).reshape(3, 4), want)
    assert out.shape == k.shape


def test_the_published_preset_has_the_published_parameter_count():
    count = QKFORMER_PRESETS["qkformer-10-768"].param_count()
    assert abs(count / 64.96e6 - 1) < 0.02, count


@pytest.mark.parametrize("policy", sorted(NAMED_POLICIES))
def test_the_plan_names_every_site_and_keeps_the_new_ones_on_jnp(policy):
    cfg = get_spikingformer_config("qkformer-10-768",
                                   policy=named_policy(policy))
    rows = cfg.execution_plan(BATCH)
    assert {r.site for r in rows} == site_tables()["qkformer"]
    for r in rows:
        if r.site.startswith(("speds.", "qkta.")):
            assert r.effective == "jnp", r
        if r.site in FLOAT_INPUT_SITES:
            assert r.effective in ("jnp", "pallas"), r


def test_train_vision_trains_the_smoke_preset():
    from repro.launch.train import train_vision

    _, history = train_vision(get_spikingformer_config("qkformer-smoke"),
                              steps=2, global_batch=4, ckpt_dir=None,
                              log_every=1)
    assert len(history) == 2 and np.all(np.isfinite(history)), history


def test_the_train_entry_point_resolves_the_published_preset():
    from types import SimpleNamespace

    from repro.launch.train import _resolve_config

    args = SimpleNamespace(arch="qkformer-10-768@jnp", reduced=False,
                           data_vocab=None, seq=None, policy=None,
                           time_chunk=None)
    cfg = _resolve_config(args)
    assert cfg == dataclasses.replace(QKFORMER_PRESETS["qkformer-10-768"],
                                      policy=named_policy("jnp"))
    assert cfg.family == "vision"


def test_the_train_entry_point_rejects_a_time_chunk_for_qkformer():
    from types import SimpleNamespace

    from repro.launch.train import _resolve_config

    args = SimpleNamespace(arch="qkformer-smoke", reduced=False,
                           data_vocab=None, seq=None, policy=None,
                           time_chunk=2)
    with pytest.raises(SystemExit, match="--time-chunk"):
        _resolve_config(args)


@pytest.mark.parametrize("policy", ["pallas", "pallas-full"])
def test_the_kernel_presets_agree_with_jnp(policy):
    """The SSA and MLP sites run the shared LIF, BN, fused-epilogue and
    packed-attention kernels (interpret mode on the CPU) where the preset
    asks; loss and gradients agree with the jnp arm to float32 rounding."""
    images, labels = _inputs()
    out = []
    for cfg in (CFG, get_spikingformer_config(
            "qkformer-smoke", policy=named_policy(policy))):
        params, state = _start()
        grads, _, metrics = jax.jit(lambda p, s: qkformer_grad_step(
            p, s, images, labels, cfg))(params, state)
        out.append((float(metrics["loss"]), grads))
    (loss, grads), (loss_k, grads_k) = out
    assert abs(loss - loss_k) <= LOSS_TOL
    assert max(_leaf_gaps(grads_k, grads).values()) <= GRAD_TOL
