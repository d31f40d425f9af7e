"""Compile rehearsal: every Pallas kernel of the Spikingformer train path,
compiled by the TPU compiler for a described v5e chip at the
``spikingformer-8-512`` geometry and the batch ``chip_smoke.py`` trains at.

Nothing runs: the compiler refuses here what it would refuse on the chip
(misaligned blocks, ops Mosaic cannot lower, scoped-VMEM overflows), at
no chip time. The topology is described inside a module fixture — never at
import — and the tests skip where it cannot be described. Kernels are
passed ``interpret=False`` because the process itself sees only the CPU.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.configs.spikingformer import get_spikingformer_config
from repro.core.spikingformer import fused_site_geometries
from repro.kernels import (conv_spike, fused_bn, lif_soma, neuron_layer,
                           spike_matmul)

#: The batch chip_smoke.py settles on: its first candidate fits a v5e.
BATCH = chip_smoke.BATCH_CANDIDATES[0]


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e:2x2 host. The persistent compile
    cache is off meanwhile: an entry compiled for a described chip cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _geo(batch=BATCH):
    return fused_site_geometries(
        get_spikingformer_config("spikingformer-8-512"), batch)


@pytest.mark.parametrize("site", ["pssa.qkv", "smlp.a", "tokenizer.conv.0"])
def test_lif_soma_compiles(one_chip, site):
    """The SN behind each site, over the site's (T, M, K) output."""
    t, m, _, k = _geo()[site]
    _compile(lambda x: lif_soma.lif_soma_fwd(x, interpret=False), one_chip,
             (t, m, k))
    _compile(lambda g_, u, s, k_: lif_soma.lif_soma_bwd(
        g_, u, s, k_, interpret=False), one_chip, *[(t, m, k)] * 4)


@pytest.mark.parametrize("site", ["tokenizer.conv.0", "tokenizer.conv.3",
                                  "pssa.proj", "smlp.a"])
def test_row_tiled_bn_compiles(one_chip, site):
    """Paper-size rows: 401408 at the first tokenizer stage, and a ragged
    last row block (6272 rows) at the linear sites."""
    t, m, _, k = _geo()[site]
    rows = t * m
    _compile(lambda x, g_, b: fused_bn.bn_fwd(x, g_, b, interpret=False),
             one_chip, (rows, k), (k,), (k,))
    _compile(lambda g_, x, ga, mu, sq: fused_bn.bn_bwd(
        g_, x, ga, mu, sq, interpret=False), one_chip,
        (rows, k), (rows, k), (k,), (1, k), (1, k))


@pytest.mark.parametrize("site", ["pssa.proj", "smlp.b"])
def test_spike_matmul_compiles(one_chip, site):
    t, m, c, k = _geo()[site]
    _compile(lambda x, w: spike_matmul.spike_matmul(x, w, interpret=False),
             one_chip, (t * m, c), (c, k))


def test_packed_attention_compiles(one_chip):
    cfg = get_spikingformer_config("spikingformer-8-512")
    g = cfg.time_steps * BATCH * cfg.n_heads
    n, dh = cfg.num_tokens, cfg.d_model // cfg.n_heads
    _compile(lambda q, kt: spike_matmul.spike_matmul_batched(
        q, kt, interpret=False), one_chip, (g, n, dh), (g, dh, n))


@pytest.mark.parametrize("stage", [1, 2, 3])
def test_spike_patch_matmul_compiles(one_chip, stage):
    t, m, c, k = _geo()[f"tokenizer.conv.{stage}"]
    _compile(lambda p, w: conv_spike.spike_patch_matmul(
        p, w, interpret=False), one_chip, (t, m, c), (c, k))


@pytest.mark.parametrize("site", ["pssa.qkv", "smlp.a"])
def test_neuron_layer_train_compiles_where_planned(one_chip, site):
    """The train arm at the largest batch whose VMEM estimate the budget
    admits — the geometry the plan keeps on the single launch."""
    batch = max(b for b in range(1, BATCH + 1)
                if neuron_layer.train_arm_vmem_bytes(
                    *_geo(b)[site], packed=True)
                <= neuron_layer.TRAIN_ARM_VMEM_BUDGET)
    t, m, c, k = _geo(batch)[site]
    _compile(lambda x, w, g_, b: neuron_layer.neuron_layer_train(
        x, w, g_, b, packed=True, interpret=False), one_chip,
        (t, m, c), (c, k), (k,), (k,))


def test_neuron_layer_train_over_budget_is_refused(one_chip):
    """The estimate is calibrated against the compiler: a site it prices
    over the budget does not compile, which is why the plan demotes it."""
    t, m, c, k = _geo(6)["pssa.qkv"]
    assert neuron_layer.train_arm_vmem_bytes(t, m, c, k, packed=True) > \
        neuron_layer.TRAIN_ARM_VMEM_BUDGET
    with pytest.raises(Exception, match="vmem"):
        _compile(lambda x, w, g_, b: neuron_layer.neuron_layer_train(
            x, w, g_, b, packed=True, interpret=False), one_chip,
            (t, m, c), (c, k), (k,), (k,))


@pytest.mark.parametrize("site,packed", [("tokenizer.conv.0", False),
                                         ("tokenizer.conv.3", True),
                                         ("pssa.qkv", True)])
def test_neuron_layer_eval_compiles(one_chip, site, packed):
    t, m, c, k = _geo()[site]
    _compile(lambda x, w, b: neuron_layer.neuron_layer_eval(
        x, w, b, packed=packed, interpret=False), one_chip,
        (t, m, c), (c, k), (k,))


def test_batch_sizing_compiles_the_train_step(v5e):
    """chip_smoke's sizing phase at the smoke size: the whole pallas-full
    train step compiles for the chip, kernels included, and the largest
    candidate batch whose plan fits the capacity wins."""
    import dataclasses

    cfg = chip_smoke.arm_config("pallas-full", "spikingformer-smoke")
    cfg = cfg.with_policy(dataclasses.replace(cfg.policy, interpret=False))
    need = chip_smoke.step_bytes(cfg, 4, v5e)
    assert need > 0
    assert chip_smoke.choose_batch({"pallas-full": cfg}, v5e, 2 * need,
                                   candidates=(64, 4)) == (
                                       4, {"pallas-full": need})
