"""The Spikingformer blocks run as a loop over depth, not a ``lax.scan``.

Parameters and BN state stay stacked over depth ([L, ...] leaves); only the
apply runs block by block, so BPTT keeps no residual stacked over depth.
The reference here is the formulation the loop replaced: a ``lax.scan``
over ``block_apply`` with the stacked trees as its ``xs``.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.spikingformer import get_spikingformer_config
from repro.core.policy import named_policy
from repro.core.spiking_layers import block_apply, linear_apply
from repro.core.spikingformer import (cross_entropy, init_spikingformer,
                                      spikingformer_apply, spikingformer_loss,
                                      tokenizer_apply)

L, T, BATCH = 3, 4, 2
CFG = dataclasses.replace(
    get_spikingformer_config("spikingformer-smoke", policy=named_policy("jnp")),
    num_layers=L, time_steps=T)
KEY = jax.random.PRNGKey(0)


def _scan_apply(params, state, images, cfg, *, train):
    """``spikingformer_apply`` with the blocks under a depth scan."""
    images = jnp.broadcast_to(images[None], (cfg.time_steps,) + images.shape)
    x, s_tok = tokenizer_apply(params["tokenizer"], state["tokenizer"],
                               images, cfg, train=train)

    def layer(x, ps):
        p, s = ps
        return block_apply(p, s, x, cfg.block, train=train)

    if cfg.remat:
        layer = jax.checkpoint(layer)
    x, s_blocks = jax.lax.scan(layer, x, (params["blocks"], state["blocks"]))
    feat = jnp.mean(x, axis=(0, 2))
    logits = linear_apply(params["head"], feat) + params["head"]["b"]
    return logits.astype(jnp.float32), {"tokenizer": s_tok, "blocks": s_blocks}


def _value_and_grad(apply, params, state, images, labels, cfg, train):
    def loss(p):
        logits, new_state = apply(p, state, images, cfg, train=train)
        return cross_entropy(logits, labels), (logits, new_state)
    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


def _assert_trees_close(got, want, what):
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    assert jax.tree.structure(got) == jax.tree.structure(want), what
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        assert g.shape == w.shape, (what, jax.tree_util.keystr(path))
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        gap = np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30)
        assert gap <= 1e-5, (what, jax.tree_util.keystr(path), gap)


@pytest.fixture(scope="module")
def inputs():
    params, state = init_spikingformer(KEY, CFG)
    images = jax.random.uniform(jax.random.PRNGKey(1),
                                (BATCH, CFG.image_size, CFG.image_size, 3))
    labels = jnp.arange(BATCH) % CFG.num_classes
    return params, state, images, labels


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("train", [True, False])
def test_loop_matches_the_depth_scan(inputs, train, remat):
    """Logits, new BN state and the gradient of every parameter leaf,
    the stacked block leaves included, agree with the scan."""
    params, state, images, labels = inputs
    cfg = dataclasses.replace(CFG, remat=remat)
    (_, (logits, new_state)), grads = _value_and_grad(
        spikingformer_apply, params, state, images, labels, cfg, train)
    (_, (ref_logits, ref_state)), ref_grads = _value_and_grad(
        _scan_apply, params, state, images, labels, cfg, train)
    _assert_trees_close(logits, ref_logits, "logits")
    _assert_trees_close(new_state, ref_state, "state")
    _assert_trees_close(grads, ref_grads, "grads")
    assert jax.tree.leaves(grads["blocks"])[0].shape[0] == L


def _scan_lengths(jaxpr) -> list[int]:
    """Length of every ``scan`` in a jaxpr, nested ones included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _scan_lengths(sub)
    return out


def test_backward_has_no_scan_over_depth(inputs):
    params, state, images, labels = inputs
    grad = jax.grad(lambda p: spikingformer_loss(p, state, images, labels,
                                                 CFG)[0])
    lengths = _scan_lengths(jax.make_jaxpr(grad)(params).jaxpr)
    assert T in lengths            # the LIF time scans are still there
    assert L not in lengths, lengths


def test_compiled_step_stacks_no_activation_over_depth(inputs):
    """No array of the optimized step leads with (L, T): a residual
    stacked over depth would be [L, T, B, ...]."""
    params, state, images, labels = inputs
    grad = jax.jit(jax.grad(lambda p: spikingformer_loss(
        p, state, images, labels, CFG)[0]))
    text = grad.lower(params).compile().as_text()
    assert re.search(rf"\[{T},{BATCH},", text)   # the check can match
    stacked = re.findall(rf"\b[a-z0-9]+\[{L},{T},[0-9,]*\]", text)
    assert not stacked, sorted(set(stacked))


def test_every_block_op_stays_under_the_blocks_scope(inputs):
    """With L blocks traced one by one, every op of a block site, forward
    and backward, still carries the ``blocks`` scope."""
    from test_named_scopes import _names

    params, state, images, labels = inputs
    grad = jax.jit(jax.grad(lambda p: spikingformer_loss(
        p, state, images, labels, CFG)[0]))
    stacks = re.findall(r'op_name="([^"]*)"',
                        grad.lower(params).compile().as_text())
    block_sites = {"pssa.qkv", "pssa.lif", "pssa.proj", "attn_qk", "attn_av",
                   "smlp.lif", "smlp.a", "smlp.b"}
    in_blocks = [s for s in stacks if _names(s) & block_sites]
    assert any("transpose(jvp(blocks))" in s for s in in_blocks)
    for stack in in_blocks:
        assert "blocks" in _names(stack), stack
