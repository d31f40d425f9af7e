"""LIF neuron + BPTT correctness (paper eq. 1-3, 11-12)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.backend import BACKENDS
from repro.core.policy import ExecutionPolicy
from repro.core.lif import (LIFConfig, lif_reference_manual_grad, lif_scan,
                            lif_scan_with_state, lif_step)

KEY = jax.random.PRNGKey(0)


def test_spikes_are_binary():
    x = jax.random.normal(KEY, (6, 32, 16)) * 3
    s = lif_scan(x, LIFConfig())
    assert set(np.unique(np.asarray(s))) <= {0.0, 1.0}


def test_fire_threshold_semantics():
    cfg = LIFConfig(alpha=0.5, th_fire=1.0)
    u, s = lif_step(jnp.zeros(4), jnp.zeros(4),
                    jnp.array([0.5, 0.99, 1.0, 2.0]), cfg)
    assert np.array_equal(np.asarray(s), [0, 0, 1, 1])


def test_hard_reset():
    """After a spike the membrane restarts from 0 (eq. 11 reset term)."""
    cfg = LIFConfig(alpha=0.5, th_fire=1.0)
    x = jnp.array([[2.0], [0.0], [0.0]])          # spike at t=0, then decay
    s = lif_scan(x, cfg)
    assert np.asarray(s)[0, 0] == 1
    # u1 = alpha * u0 * (1 - s0) + 0 = 0 -> no spike forever after
    assert np.asarray(s)[1:].sum() == 0


def test_leak_accumulation():
    cfg = LIFConfig(alpha=0.5, th_fire=1.0)
    x = jnp.full((3, 1), 0.6)
    s = np.asarray(lif_scan(x, cfg))
    # u0=0.6 (no), u1=0.9 (no), u2=1.05 (spike)
    assert s.tolist() == [[0.0], [0.0], [1.0]]


@pytest.mark.parametrize("alpha", [0.3, 0.5, 0.9])
@pytest.mark.parametrize("t", [1, 4, 9])
def test_bptt_matches_eq12(alpha, t):
    cfg = LIFConfig(alpha=alpha)
    x = jax.random.normal(jax.random.PRNGKey(t), (t, 33)) * 2
    g = jax.random.normal(jax.random.PRNGKey(t + 1), (t, 33))
    auto = jax.vjp(lambda xs: lif_scan(xs, cfg), x)[1](g)[0]
    manual = lif_reference_manual_grad(x, g, cfg)
    assert jnp.allclose(auto, manual, atol=1e-5)


@pytest.mark.parametrize("alpha", [0.3, 0.5, 0.9])
def test_bptt_matches_eq12_pallas(alpha):
    """Same eq. 12 check through the fused SOMA/GRAD backend (t=4; each
    (t, alpha) pair is a fresh interpret-mode trace, so one t suffices —
    the t sweep runs on the jnp path above and in test_kernels.py)."""
    cfg = LIFConfig(alpha=alpha, policy=ExecutionPolicy(backend="pallas"))
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 33)) * 2
    g = jax.random.normal(jax.random.PRNGKey(5), (4, 33))
    auto = jax.vjp(lambda xs: lif_scan(xs, cfg), x)[1](g)[0]
    manual = lif_reference_manual_grad(x, g, cfg)
    assert jnp.allclose(auto, manual, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_forward_parity(backend):
    """lif_scan spikes are bit-identical across backends (binary outputs)."""
    x = jax.random.normal(KEY, (4, 3, 5, 16)) * 2
    ref = lif_scan(x, LIFConfig())
    got = lif_scan(x, LIFConfig(policy=ExecutionPolicy(backend=backend)))
    assert jnp.array_equal(ref, got)


def test_lif_three_way_grad_agreement():
    """lax.scan autodiff vs fused SOMA/GRAD op vs hand-rolled eq. 12 —
    all three produce the same dL/dX to 1e-5."""
    from repro.kernels import ops

    cfg = LIFConfig()
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 6, 24)) * 2
    g = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    via_scan = jax.vjp(lambda a: lif_scan(a, cfg), x)[1](g)[0]
    via_op = jax.vjp(ops.lif_soma_op, x)[1](g)[0]
    manual = lif_reference_manual_grad(x, g, cfg)
    assert jnp.allclose(via_scan, via_op, atol=1e-5)
    assert jnp.allclose(via_op, manual, atol=1e-5)
    assert jnp.allclose(via_scan, manual, atol=1e-5)


def test_streaming_state_continuity():
    cfg = LIFConfig()
    x = jax.random.normal(KEY, (8, 17)) * 2
    full = lif_scan(x, cfg)
    s1, carry = lif_scan_with_state(x[:4], jnp.zeros(17), jnp.zeros(17), cfg)
    s2, _ = lif_scan_with_state(x[4:], *carry, cfg)
    assert jnp.allclose(jnp.concatenate([s1, s2]), full)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("chunk", [1, 2, 3, 4, 12])
def test_streaming_chunked_matches_single_scan(backend, chunk):
    """Chunk-by-chunk ``lif_scan_with_state`` == one ``lif_scan`` over the
    concatenated sequence, for every chunking and backend (the stateful
    dispatch underpins the time-chunked training scan). Spikes are binary,
    so the match is bitwise."""
    cfg = LIFConfig(policy=ExecutionPolicy(backend=backend))
    x = jax.random.normal(jax.random.PRNGKey(7), (12, 3, 8)) * 2
    full = lif_scan(x, cfg)
    u = jnp.zeros((3, 8))
    s = jnp.zeros((3, 8))
    outs = []
    for i in range(0, 12, chunk):
        out, (u, s) = lif_scan_with_state(x[i:i + chunk], u, s, cfg)
        outs.append(out)
    assert jnp.array_equal(jnp.concatenate(outs), full)


@pytest.mark.parametrize("backend", BACKENDS)
def test_stateful_carry_grads_match_eq12(backend):
    """BPTT through a 2-chunk stateful split == the single-scan gradient ==
    hand-rolled eq. 12 — the carry cotangents (du, ds across the boundary)
    are exact under both backends."""
    cfg = LIFConfig(policy=ExecutionPolicy(backend=backend))
    x = jax.random.normal(jax.random.PRNGKey(8), (6, 21)) * 2
    g = jax.random.normal(jax.random.PRNGKey(9), (6, 21))

    def split_scan(xs):
        z = jnp.zeros_like(xs[0])
        s1, (u, s) = lif_scan_with_state(xs[:3], z, z, cfg)
        s2, _ = lif_scan_with_state(xs[3:], u, s, cfg)
        return jnp.concatenate([s1, s2])

    via_split = jax.vjp(split_scan, x)[1](g)[0]
    via_scan = jax.vjp(lambda a: lif_scan(a, cfg), x)[1](g)[0]
    manual = lif_reference_manual_grad(x, g, cfg)
    assert jnp.allclose(via_split, via_scan, atol=1e-6)
    assert jnp.allclose(via_split, manual, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("time_chunk", [1, 3, 6])
def test_time_chunk_scan_exact(backend, time_chunk):
    """``LIFConfig.time_chunk`` tiling: forward bitwise, gradients exact
    (to float fma noise at chunk boundaries under pallas)."""
    base = LIFConfig(policy=ExecutionPolicy(backend=backend))
    import dataclasses
    cfg = dataclasses.replace(base, time_chunk=time_chunk)
    x = jax.random.normal(jax.random.PRNGKey(10), (6, 4, 9)) * 2
    g = jax.random.normal(jax.random.PRNGKey(11), x.shape)
    assert jnp.array_equal(lif_scan(x, cfg), lif_scan(x, base))
    d_tiled = jax.vjp(lambda a: lif_scan(a, cfg), x)[1](g)[0]
    d_full = jax.vjp(lambda a: lif_scan(a, base), x)[1](g)[0]
    assert jnp.allclose(d_tiled, d_full, atol=1e-6)


@settings(max_examples=30, deadline=None)
@given(alpha=st.floats(0.05, 0.95), scale=st.floats(0.1, 5.0),
       seed=st.integers(0, 2 ** 16))
def test_membrane_bounded_property(alpha, scale, seed):
    """Invariant: with hard reset, |U| can never exceed
    max|x| / (1 - alpha) between spikes."""
    cfg = LIFConfig(alpha=alpha)
    x = jax.random.normal(jax.random.PRNGKey(seed), (12, 8)) * scale

    def step(carry, xt):
        u, s = carry
        u2, s2 = lif_step(u, s, xt, cfg)
        return (u2, s2), u2

    (_, _), us = jax.lax.scan(step, (jnp.zeros(8), jnp.zeros(8)), x)
    bound = jnp.max(jnp.abs(x)) / (1 - alpha) + 1e-4
    assert float(jnp.max(jnp.abs(us))) <= float(bound)
