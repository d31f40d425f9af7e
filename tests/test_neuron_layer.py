"""Single-launch neuron-layer megakernel (matmul + BN + SOMA in one Pallas
kernel) and its ``fused_epilogue`` registry impls.

Parity contract (the ISSUE 5 acceptance numbers): forward spikes bitwise
and gradients <= 1e-5 against the jnp reference at every site the fused
epilogue can serve — the Q/K/V and SMLP-A Conv1DBN->SN pairs and every
eq. 4 tokenizer stage — for float and spike inputs, with and without
``time_chunk`` tiling. Plus hypothesis property tests for the im2col
lowering on odd spatial sizes and stride-2 edge shapes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.lif import LIFConfig, lif_scan
from repro.core.policy import ExecutionPolicy, available_impls, named_policy
from repro.core.spiking_layers import (init_linear_bn, linear_bn_apply,
                                       linear_bn_lif_apply)
from repro.core.spikingformer import (SpikingFormerConfig, init_spikingformer,
                                      init_tokenizer, spikingformer_loss,
                                      tokenizer_apply)
from repro.kernels import ops
from repro.kernels.conv_spike import conv_w_matrix, im2col, same_padding

KEY = jax.random.PRNGKey(0)
FULL = named_policy("pallas-full")
JNP = named_policy("jnp")


def _close(a, b, atol=1e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)


def _tree_close(ta, tb, atol=1e-5):
    for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
        _close(a, b, atol=atol)


def _grad_tree_close(ta, tb, atol=1e-5):
    """Scale-aware 1e-5 (the repo's gradient-parity convention, see
    test_spikingformer._grad_trees_close): identical VJP math, different
    fp32 reduction orders, so noise scales with gradient magnitude."""
    for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
        a, b = np.asarray(a), np.asarray(b)
        scale = max(1.0, float(np.max(np.abs(b))))
        np.testing.assert_allclose(a / scale, b / scale, atol=atol)


# ---------------------------------------------------------------------------
# Op level: the megakernel vs the 3-launch math it replaces
# ---------------------------------------------------------------------------

def _reference_neuron_layer(x, w, gamma, beta, eps=1e-5):
    """matmul -> train-mode BN (batch stats over T*M) -> LIF, in jnp."""
    z = jnp.einsum("tmc,ck->tmk", x, w)
    zf = z.reshape(-1, z.shape[-1])
    mu = jnp.mean(zf, axis=0)
    var = jnp.maximum(jnp.mean(zf * zf, axis=0) - mu * mu, 0.0)
    y = gamma * (z - mu) / jnp.sqrt(var + eps) + beta
    return lif_scan(y, LIFConfig()), mu, var


@pytest.mark.parametrize("packed", [False, True])
def test_neuron_layer_train_op_forward_and_stats(packed):
    t, m, c, k = 2, 24, 40, 16
    x = (jax.random.uniform(KEY, (t, m, c)) < 0.3).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (c, k)) / c ** 0.5
    gamma = jax.random.uniform(jax.random.PRNGKey(2), (k,)) + 0.5
    beta = jax.random.normal(jax.random.PRNGKey(3), (k,)) * 0.1
    s, mu, var = ops.neuron_layer_train_op(x, w, gamma, beta, 0.5, 1.0, 0.0,
                                           2.0, 1.0, 1e-5, packed, True)
    s_r, mu_r, var_r = _reference_neuron_layer(x, w, gamma, beta)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_r))
    _close(mu, mu_r, atol=1e-6)
    _close(var, var_r, atol=1e-6)


@pytest.mark.parametrize("packed", [False, True])
def test_neuron_layer_train_op_grads_replay_matches_autodiff(packed):
    """The replay backward (recomputed pre-activation -> GRAD kernel ->
    eq. 19-23 BN backward -> dense matmul VJP) == autodiff through the jnp
    reference chain, for all four inputs, to 1e-5."""
    t, m, c, k = 2, 20, 32, 24
    x = (jax.random.uniform(KEY, (t, m, c)) < 0.3).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (c, k)) / c ** 0.5
    gamma = jax.random.uniform(jax.random.PRNGKey(2), (k,)) + 0.5
    beta = jax.random.normal(jax.random.PRNGKey(3), (k,)) * 0.1

    def loss(fn):
        # cumsum makes the upstream cotangent time-dependent, exercising the
        # full temporal GRAD recursion, not just the last step.
        return lambda *a: jnp.sum(jnp.cumsum(fn(*a), axis=0) ** 2)

    g_r = jax.grad(loss(lambda *a: _reference_neuron_layer(*a)[0]),
                   argnums=(0, 1, 2, 3))(x, w, gamma, beta)
    g_f = jax.grad(loss(lambda xx, ww, gm, bt: ops.neuron_layer_train_op(
        xx, ww, gm, bt, 0.5, 1.0, 0.0, 2.0, 1.0, 1e-5, packed, True)[0]),
        argnums=(0, 1, 2, 3))(x, w, gamma, beta)
    _tree_close(g_r, g_f)


def test_neuron_layer_eval_op_matches_folded_reference():
    t, m, c, k = 2, 16, 24, 16
    x = (jax.random.uniform(KEY, (t, m, c)) < 0.4).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (c, k)) / c ** 0.5
    gamma = jax.random.uniform(jax.random.PRNGKey(2), (k,)) + 0.5
    beta = jax.random.normal(jax.random.PRNGKey(3), (k,)) * 0.1
    mean = jax.random.normal(jax.random.PRNGKey(4), (k,)) * 0.3
    var = jax.random.uniform(jax.random.PRNGKey(5), (k,)) + 0.5
    from repro.kernels.conv_spike import fold_bn

    w_f, bias = fold_bn(w, gamma, beta, mean, var)
    s = ops.neuron_layer_eval_op(x, w_f.astype(x.dtype), bias, 0.5, 1.0,
                                 0.0, 2.0, 1.0, True, True)
    y = gamma * (jnp.einsum("tmc,ck->tmk", x, w) - mean) \
        / jnp.sqrt(var + 1e-5) + beta
    np.testing.assert_array_equal(np.asarray(s),
                                  np.asarray(lif_scan(y, LIFConfig())))
    # gradients flow through the folded weights/bias
    g = jax.grad(lambda xx: jnp.sum(ops.neuron_layer_eval_op(
        xx, w_f.astype(x.dtype), bias, 0.5, 1.0, 0.0, 2.0, 1.0, True,
        True) ** 2))(x)
    g_r = jax.grad(lambda xx: jnp.sum(lif_scan(
        gamma * (jnp.einsum("tmc,ck->tmk", xx, w) - mean)
        / jnp.sqrt(var + 1e-5) + beta, LIFConfig()) ** 2))(x)
    _close(g, g_r)


# ---------------------------------------------------------------------------
# Site level: fused_epilogue at every linear_bn site it can serve
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("site,d_in,d_out", [
    ("pssa.qkv", 32, 32), ("smlp.a", 32, 64)])
@pytest.mark.parametrize("time_chunk", [None, 1])
def test_fused_epilogue_linear_site_parity(site, d_in, d_out, time_chunk):
    """The Conv1DBN->SN pair under fused_epilogue == the jnp pipeline:
    spikes bitwise, BN state and all gradients <= 1e-5, train and eval,
    with and without time_chunk tiling (the fused op runs single-shot —
    exactly what the tiled reference computes)."""
    params, state = init_linear_bn(jax.random.PRNGKey(2), d_in, d_out)
    xs = (jax.random.uniform(jax.random.PRNGKey(3), (2, 2, 16, d_in)) < 0.3
          ).astype(jnp.float32)
    lif_j = LIFConfig(time_chunk=time_chunk, policy=JNP)
    lif_f = LIFConfig(time_chunk=time_chunk, policy=FULL)

    def run(pol, lif, train):
        return linear_bn_lif_apply(params, state, xs, lif, train=train,
                                   policy=pol, site=site, lif_site="t.lif")

    yj, stj = run(JNP, lif_j, True)
    yf, stf = run(FULL, lif_f, True)
    np.testing.assert_array_equal(np.asarray(yj), np.asarray(yf))
    _tree_close(stj, stf)

    def grads(pol, lif):
        def loss(p, xx):
            y, _ = linear_bn_lif_apply(p, state, xx, lif, train=True,
                                       policy=pol, site=site,
                                       lif_site="t.lif")
            return jnp.sum(jnp.cumsum(y, axis=0) ** 2)
        return jax.grad(loss, argnums=(0, 1))(params, xs)

    _grad_tree_close(grads(JNP, lif_j), grads(FULL, lif_f))

    ej, _ = run(JNP, lif_j, False)
    ef, _ = run(FULL, lif_f, False)
    np.testing.assert_array_equal(np.asarray(ej), np.asarray(ef))


def test_fused_epilogue_ragged_contraction_dense_arm(caplog):
    """A ragged (% 8 != 0) contraction keeps the single launch on the dense
    arm — numerically identical, logged as a WARNING."""
    import logging

    from repro.core import policy as policy_mod

    params, state = init_linear_bn(jax.random.PRNGKey(2), 36, 32)
    xs = (jax.random.uniform(jax.random.PRNGKey(3), (2, 2, 8, 36)) < 0.3
          ).astype(jnp.float32)
    policy_mod._reported_fallbacks.clear()
    with caplog.at_level(logging.INFO, logger="repro.execution"):
        yf, _ = linear_bn_lif_apply(params, state, xs, LIFConfig(policy=FULL),
                                    train=True, policy=FULL, site="pssa.qkv",
                                    lif_site="t.lif")
    yj, _ = linear_bn_lif_apply(params, state, xs, LIFConfig(), train=True,
                                policy=JNP, site="pssa.qkv", lif_site="t.lif")
    np.testing.assert_array_equal(np.asarray(yj), np.asarray(yf))
    warn = [r for r in caplog.records if r.levelno == logging.WARNING]
    assert warn and "% 8" in warn[0].getMessage()
    assert "still fused" in warn[0].getMessage()


def test_plain_linear_bn_apply_demotes_fused_epilogue(caplog):
    """A site with no trailing LIF reached through plain linear_bn_apply
    demotes to the pipeline fallback (INFO, the plan already predicted it)
    and still returns the pre-activation."""
    import logging

    from repro.core import policy as policy_mod

    params, state = init_linear_bn(jax.random.PRNGKey(2), 32, 32)
    x = (jax.random.uniform(jax.random.PRNGKey(3), (4, 32)) < 0.3
         ).astype(jnp.float32)
    policy_mod._reported_fallbacks.clear()
    with caplog.at_level(logging.INFO, logger="repro.execution"):
        yf, _ = linear_bn_apply(params, state, x, train=True, policy=FULL,
                                site="smlp.b")
    yj, _ = linear_bn_apply(params, state, x, train=True, policy=JNP,
                            site="smlp.b")
    _close(yf, yj)
    msgs = [r for r in caplog.records if "no trailing LIF" in r.getMessage()]
    assert msgs and msgs[0].levelno == logging.INFO
    assert "fused_epilogue" in available_impls("linear_bn")
    assert "fused_epilogue" in available_impls("conv")


# ---------------------------------------------------------------------------
# Model level: pallas-full (megakernel everywhere) vs jnp, incl. time_chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spike_input", [False, True])
@pytest.mark.parametrize("time_chunk", [None, 2])
def test_model_parity_with_megakernel(spike_input, time_chunk):
    """End-to-end: loss to 1e-6, grads scale-aware 1e-5 vs jnp — float and
    pre-encoded spike frames, single-shot and temporally tiled."""
    cfg_j = SpikingFormerConfig(
        num_layers=1, d_model=32, n_heads=2, d_ff=64, time_steps=4,
        image_size=16, patch_grid=4, num_classes=4, time_chunk=time_chunk,
        in_channels=8 if spike_input else 3, spike_input=spike_input)
    cfg_f = cfg_j.with_policy(FULL)
    params, state = init_spikingformer(KEY, cfg_j)
    x = jax.random.uniform(jax.random.PRNGKey(11),
                           (4, 2, 16, 16, cfg_j.in_channels))
    if spike_input:
        x = (x < 0.4).astype(jnp.float32)
    labels = jnp.array([0, 1])

    grad_fn = jax.jit(jax.value_and_grad(spikingformer_loss, has_aux=True),
                      static_argnums=4)
    (lj, (stj, _)), gj = grad_fn(params, state, x, labels, cfg_j)
    (lf, (stf, _)), gf = grad_fn(params, state, x, labels, cfg_f)
    np.testing.assert_allclose(float(lj), float(lf), atol=1e-6)
    _tree_close(stj, stf)
    _grad_tree_close(gj, gf)


def test_tokenizer_megakernel_time_chunk_exact():
    """time_chunk exactness through the megakernel tokenizer: outputs and
    gradients are the single-shot values bit-for-bit regardless of tiling
    (the fused op's replay backward subsumes the tiled memory profile)."""
    cfg = SpikingFormerConfig(num_layers=1, d_model=32, n_heads=2, d_ff=64,
                              time_steps=4, image_size=16, patch_grid=4,
                              num_classes=4, policy=FULL)
    params, state = init_tokenizer(KEY, cfg)
    x = jax.random.uniform(jax.random.PRNGKey(8), (4, 2, 16, 16, 3))

    def grads(cfg):
        def loss(p, xx):
            y, _ = tokenizer_apply(p, state, xx, cfg, train=True)
            return jnp.mean(y ** 2)
        return jax.grad(loss, argnums=(0, 1))(params, x)

    y, _ = tokenizer_apply(params, state, x, cfg, train=True)
    g = grads(cfg)
    for tc in (1, 2):
        cfg_tc = dataclasses.replace(cfg, time_chunk=tc)
        y_tc, _ = tokenizer_apply(params, state, x, cfg_tc, train=True)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_tc))
        _tree_close(g, grads(cfg_tc), atol=1e-6)


# ---------------------------------------------------------------------------
# Property tests: same_padding / im2col on odd sizes and stride-2 edges
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(size=st.integers(1, 64), kernel=st.integers(1, 5),
       stride=st.integers(1, 3))
def test_same_padding_properties(size, kernel, stride):
    """XLA SAME semantics: output = ceil(size/stride), padding covers every
    window, hi >= lo (XLA puts the odd pad at the end), both >= 0."""
    lo, hi = same_padding(size, kernel, stride)
    out = -(-size // stride)
    assert lo >= 0 and hi >= 0
    assert hi - lo in (0, 1)
    assert (out - 1) * stride + kernel <= size + lo + hi
    # the padding is minimal: one less would not cover the last window
    assert lo + hi == max((out - 1) * stride + kernel - size, 0)


@settings(max_examples=10, deadline=None)
@given(h=st.integers(4, 19), w=st.integers(4, 19), c=st.integers(1, 5),
       co=st.integers(1, 4))
def test_im2col_matmul_equals_xla_conv_odd_shapes(h, w, c, co):
    """im2col(x) @ conv_w_matrix(w) == the k3/s2 SAME conv for odd spatial
    sizes and stride-2 edge shapes (where the asymmetric SAME padding and
    the ragged final window bite)."""
    x = jax.random.normal(jax.random.PRNGKey(h * 100 + w), (2, h, w, c))
    wt = jax.random.normal(jax.random.PRNGKey(c * 10 + co), (3, 3, c, co))
    ref = jax.lax.conv_general_dilated(
        x, wt, window_strides=(2, 2), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    got = im2col(x) @ conv_w_matrix(wt)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


@settings(max_examples=6, deadline=None)
@given(t=st.integers(1, 3), m=st.integers(1, 33), c8=st.integers(1, 6),
       k=st.integers(1, 17))
def test_neuron_layer_op_parity_random_shapes(t, m, c8, k):
    """Property check: the packed megakernel forward == the jnp reference
    for arbitrary (T, M, C % 8 == 0, K) shapes, including ragged M/K tiles."""
    c = 8 * c8
    key = jax.random.PRNGKey(t * 1000 + m * 10 + c + k)
    x = (jax.random.uniform(key, (t, m, c)) < 0.3).astype(jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (c, k)) / c ** 0.5
    gamma = jnp.ones((k,)) * 1.2
    beta = jnp.zeros((k,)) + 0.1
    s, _, _ = ops.neuron_layer_train_op(x, w, gamma, beta, 0.5, 1.0, 0.0,
                                        2.0, 1.0, 1e-5, True, True)
    s_r, _, _ = _reference_neuron_layer(x, w, gamma, beta)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_r))
