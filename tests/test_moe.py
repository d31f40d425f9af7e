"""MoE unit + property tests (single-device local path)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.common import split_tree
from repro.models.moe import (MoEConfig, _expert_positions, _route, init_moe,
                              moe_apply)

KEY = jax.random.PRNGKey(0)
CFG = MoEConfig(d_model=32, num_experts=8, top_k=2, d_ff_expert=16,
                capacity_factor=8.0, model_shards=1)


def _params(cfg=CFG):
    return split_tree(init_moe(KEY, cfg))[0]


def _reference_moe(params, x, cfg):
    """Dense loop-over-experts oracle (no capacity, no dispatch)."""
    n, d = x.reshape(-1, x.shape[-1]).shape
    xf = x.reshape(n, d)
    gates, experts, _ = _route(params["router"], xf, cfg)
    wg = params["w_gate"].reshape(cfg.num_experts, d, -1)
    wu = params["w_up"].reshape(cfg.num_experts, d, -1)
    wd = params["w_down"].reshape(cfg.num_experts, -1, d)
    y = jnp.zeros_like(xf)
    for i in range(n):
        for j in range(cfg.top_k):
            e = int(experts[i, j])
            h = jax.nn.silu(xf[i] @ wg[e]) * (xf[i] @ wu[e])
            y = y.at[i].add(gates[i, j] * (h @ wd[e]))
    return y.reshape(x.shape)


def test_moe_matches_dense_reference():
    p = _params()
    x = jax.random.normal(KEY, (2, 4, 32))
    y, aux = moe_apply(p, x, CFG)
    want = _reference_moe(p, x, CFG)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5,
                               rtol=1e-4)
    assert float(aux) > 0


def test_moe_gradients_flow_to_experts():
    p = _params()
    x = jax.random.normal(KEY, (2, 8, 32))

    def loss(p):
        y, aux = moe_apply(p, x, CFG)
        return jnp.sum(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["w_gate"]).sum()) > 0
    assert float(jnp.abs(g["router"]).sum()) > 0


def test_capacity_drops_tokens():
    """With capacity_factor ~ 0, every token is dropped -> y == shared-only
    (zero when no shared experts)."""
    cfg = dataclasses.replace(CFG, capacity_factor=1e-9)
    p = _params(cfg)
    x = jax.random.normal(KEY, (1, 64, 32))
    y, _ = moe_apply(p, x, cfg)
    # capacity clamps at 4 slots minimum; most of the 128 assignments drop
    dense = _reference_moe(p, x, dataclasses.replace(cfg,
                                                     capacity_factor=8.0))
    assert float(jnp.abs(y).sum()) < float(jnp.abs(dense).sum())


def test_shared_experts_always_active():
    cfg = dataclasses.replace(CFG, n_shared=1, capacity_factor=1e-9)
    p = _params(cfg)
    x = jax.random.normal(KEY, (1, 64, 32))
    y, _ = moe_apply(p, x, cfg)
    assert float(jnp.abs(y).sum()) > 0        # shared path bypasses capacity


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(2, 64),
       e=st.sampled_from([2, 4, 8, 16]))
def test_expert_positions_property(seed, n, e):
    """Positions are a valid within-expert enumeration: unique per expert,
    contiguous from 0."""
    flat_e = jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, e)
    pos = _expert_positions(flat_e, e)
    fe = np.asarray(flat_e)
    ps = np.asarray(pos)
    for ex in range(e):
        mine = sorted(ps[fe == ex])
        assert mine == list(range(len(mine)))


def test_tp_pair_layout_single_device():
    """E < M physical layout collapses correctly at M=1 (smoke regime)."""
    cfg = MoEConfig(d_model=16, num_experts=4, top_k=1, d_ff_expert=8,
                    model_shards=1, capacity_factor=8.0)
    p = _params(cfg)
    assert p["w_gate"].shape == (1, 4, 16, 8)
    x = jax.random.normal(KEY, (1, 4, 16))
    y, _ = moe_apply(p, x, cfg)
    assert y.shape == x.shape
