"""Mesh-sharded Spikingformer training semantics (the vision path through
the launch subsystem: FSDP + data/model sharding + place_batch + elastic
checkpointing).

These tests need a multi-device CPU: run them with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
``test-sharded`` leg does; ``tests/test_distributed.py`` also drives this
file in a subprocess under the slow marker so `pytest -m slow` covers it
without the env flag). On a single-device process they skip.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

from repro.configs.spikingformer import get_spikingformer_config  # noqa: E402
from repro.core.policy import named_policy  # noqa: E402
from repro.core.spikingformer import (init_spikingformer,  # noqa: E402
                                      spikingformer_loss)
from repro.launch.mesh import make_test_mesh, use_mesh  # noqa: E402
from repro.train.data import place_batch  # noqa: E402

CFG = get_spikingformer_config("spikingformer-smoke",
                               policy=named_policy("jnp"))
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def mesh():
    return make_test_mesh(4, 2)


@pytest.fixture(scope="module")
def model():
    return init_spikingformer(KEY, CFG)


@pytest.fixture(scope="module")
def batch():
    imgs = jax.random.uniform(jax.random.PRNGKey(1), (8, 32, 32, 3))
    labels = jnp.arange(8) % 10
    return np.asarray(imgs), np.asarray(labels)


def _grad_fn():
    return jax.jit(jax.value_and_grad(spikingformer_loss, has_aux=True),
                   static_argnums=4)


def _rel_err(ga, gb):
    return max(float(jnp.max(jnp.abs(a - b)))
               / max(1.0, float(jnp.max(jnp.abs(a))))
               for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)))


@pytest.mark.parametrize("policy_name", ["jnp", "pallas-full"])
def test_sharded_step_matches_single_device(mesh, model, batch, policy_name):
    """Loss + grads on the (data=4, model=2) mesh == single-device values
    to ~1e-5 (GSPMD only reorders fp32 reductions), for the reference and
    the full-Pallas policies."""
    params, state = model
    imgs, labels = batch
    pol = named_policy(policy_name)
    if policy_name != "jnp":
        pol = dataclasses.replace(pol, interpret=True)
    cfg = CFG.with_policy(pol)
    fn = _grad_fn()
    (l_ref, _), g_ref = fn(params, state, jnp.asarray(imgs),
                           jnp.asarray(labels), cfg)
    b = place_batch({"images": imgs, "labels": labels}, mesh)
    with use_mesh(mesh):
        (l_sh, _), g_sh = fn(params, state, b["images"], b["labels"], cfg)
    assert abs(float(l_ref) - float(l_sh)) < 1e-5
    assert _rel_err(g_ref, g_sh) < 1e-5


def test_time_chunk_composes_with_mesh(mesh, model, batch):
    """Temporal tiling under the sharded step: same grads as the sharded
    single-shot scan."""
    params, state = model
    imgs, labels = batch
    fn = _grad_fn()
    b = place_batch({"images": imgs, "labels": labels}, mesh)
    with use_mesh(mesh):
        (_, _), g1 = fn(params, state, b["images"], b["labels"], CFG)
        (_, _), g2 = fn(params, state, b["images"], b["labels"],
                        dataclasses.replace(CFG, time_chunk=1))
    assert _rel_err(g1, g2) < 1e-6


def test_build_state_shards_params_and_moments(mesh):
    """build_vision_state: model-parallel leaves on "model", FSDP
    leaves on "data" (stacked block leaves keep the L axis unsharded), and
    the Adam moments shard exactly like the params."""
    from repro.launch.train import build_vision_state
    from repro.train.optimizer import OptimizerConfig

    params, state, opt, (p_specs, _) = build_vision_state(
        CFG, mesh, OptimizerConfig(), fsdp_min_elems=1024)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    n_model = sum(1 for _, l in flat if "model" in str(l.sharding.spec))
    n_data = sum(1 for _, l in flat if "data" in str(l.sharding.spec))
    assert n_model >= 10 and n_data >= 5
    for path, leaf in flat:
        spec = leaf.sharding.spec
        # the stacked block leaves never shard their leading L scan axis
        if "blocks" in str(path) and len(spec) > 0:
            assert spec[0] is None, (path, spec)
    for pl, ml in zip(jax.tree.leaves(params), jax.tree.leaves(opt["m"])):
        assert pl.sharding == ml.sharding


def test_vision_train_loop_runs_on_mesh(mesh, tmp_path):
    """The unified launch driver end-to-end on the test mesh: synthetic
    vision data through place_batch, sharded steps, checkpoint, restore."""
    from repro.launch.train import train_vision
    from repro.train import checkpoint as ckpt

    d = str(tmp_path)
    _, hist = train_vision(CFG, steps=3, global_batch=8, ckpt_dir=d,
                           mesh=mesh, ckpt_every=2, log_every=10)
    assert len(hist) == 3 and all(np.isfinite(hist))
    assert ckpt.latest_step(d) == 2
    # restart resumes from the checkpoint (elastic restore path)
    _, hist2 = train_vision(CFG, steps=4, global_batch=8, ckpt_dir=d,
                            mesh=mesh, ckpt_every=10, log_every=10)
    assert len(hist2) == 2          # steps 2..3 only


def test_checkpoint_roundtrip_sharded_mesh(mesh, tmp_path):
    """Spikingformer params + opt state saved under the (4, 2) mesh restore
    onto a *different* mesh (host-count-agnostic: the saved logical specs
    re-resolve against the new mesh), values and shardings preserved."""
    from repro.launch.train import build_vision_state
    from repro.train import checkpoint as ckpt
    from repro.train.optimizer import OptimizerConfig, init_opt_specs

    params, state, opt, (p_specs, s_specs) = build_vision_state(
        CFG, mesh, OptimizerConfig(), fsdp_min_elems=1024)
    tree = {"params": params, "state": state, "opt": opt}
    specs = {"params": p_specs, "state": s_specs,
             "opt": init_opt_specs(p_specs)}
    ckpt.save_checkpoint(str(tmp_path), 7, tree, specs)

    mesh_b = make_test_mesh(2, 2)   # elastic: fewer data shards
    restored = ckpt.restore_checkpoint(str(tmp_path), 7, tree, mesh_b, specs)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert b.sharding.mesh.devices.size == 4    # lives on the new mesh
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # sharding is preserved: a model-parallel leaf stays model-parallel
    q_w = restored["params"]["blocks"]["pssa"]["q"]["linear"]["w"]
    assert "model" in str(q_w.sharding.spec)
    # moments restore with the same placement as their params
    q_m = restored["opt"]["m"]["blocks"]["pssa"]["q"]["linear"]["w"]
    assert q_m.sharding == q_w.sharding

    # host-count-agnostic: restore WITHOUT the writer's spec tree — the
    # logical specs stored in index.json re-resolve against the new mesh
    restored2 = ckpt.restore_checkpoint(str(tmp_path), 7, tree, mesh_b)
    q_w2 = restored2["params"]["blocks"]["pssa"]["q"]["linear"]["w"]
    assert "model" in str(q_w2.sharding.spec)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_vision_dryrun_cell_lowers(mesh):
    """The launch/specs.py vision cell: structs + specs line up and the
    unified train step lowers under the mesh (full compile is exercised
    ad hoc by the dry-run tool; lowering catches struct/spec drift)."""
    from jax.sharding import NamedSharding
    from repro.launch.specs import input_specs

    fn, structs, specs = input_specs(CFG, "train_4k", mesh)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s if s is not None else P()),
        specs, is_leaf=lambda x: isinstance(x, P) or x is None)
    with use_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=shardings,
                          donate_argnums=(0, 1, 2)).lower(*structs)
    assert lowered is not None


def test_describe_execution_reports_sharding_plan(mesh):
    out = CFG.describe_execution(mesh)
    assert "Sharding plan" in out
    assert "pssa.qkv,PartitionSpec(None, ('pod', 'data'), None, 'model')" \
        in out
    assert "blocks/pssa/q/linear/w" in out
