"""Optimizer, data pipeline, checkpointing, resilience."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, SyntheticLM, place_batch
from repro.train.optimizer import (OptimizerConfig, adamw_update,
                                   compress_int8, init_opt_state,
                                   lr_schedule)
from repro.train.resilience import ElasticPlan, StragglerMonitor

KEY = jax.random.PRNGKey(0)


# ------------------------------ optimizer ----------------------------------

def test_adamw_first_step_matches_reference():
    cfg = OptimizerConfig(lr=1e-2, warmup_steps=0, total_steps=10,
                          weight_decay=0.0, grad_clip=1e9)
    p = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    g = {"w": jnp.full((4, 4), 0.5), "b": jnp.ones((4,))}
    state = init_opt_state(p)
    new_p, new_state, metrics = adamw_update(p, g, state, cfg)
    # bias-corrected first step == -lr * g / (|g| + eps)
    lr0 = float(lr_schedule(cfg, jnp.ones(())))
    expect = 1.0 - lr0 * 0.5 / (0.5 + cfg.eps)
    np.testing.assert_allclose(np.asarray(new_p["w"]), expect, rtol=1e-5)
    assert int(new_state["step"]) == 1
    assert float(metrics["grad_norm"]) == pytest.approx(
        np.sqrt(16 * 0.25 + 4), rel=1e-5)


def test_grad_clip_applies():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=0, grad_clip=1.0,
                          weight_decay=0.0)
    p = {"w": jnp.zeros((10,))}
    g = {"w": jnp.full((10,), 100.0)}
    new_p, _, m = adamw_update(p, g, init_opt_state(p), cfg)
    assert float(m["grad_norm"]) > 100
    assert np.all(np.isfinite(np.asarray(new_p["w"])))


def test_lr_schedule_shape():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
    lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in range(101)]
    assert lrs[0] < lrs[9] <= 1.0                # warmup
    assert lrs[10] == pytest.approx(1.0, rel=1e-3)
    assert lrs[100] == pytest.approx(0.1, rel=1e-2)  # cosine floor


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), scale=st.floats(1e-3, 10))
def test_compress_int8_error_feedback(seed, scale):
    """Quantize-with-residual: dequantized + residual == original exactly."""
    g = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * scale
    err = jnp.zeros((64,))
    deq, new_err = compress_int8(g, err, jax.random.PRNGKey(seed + 1))
    np.testing.assert_allclose(np.asarray(deq + new_err), np.asarray(g),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(new_err))) <= \
        float(jnp.max(jnp.abs(g))) / 127 + 1e-6


# ------------------------------ data ---------------------------------------

def test_data_determinism():
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=4)
    d1, d2 = SyntheticLM(cfg), SyntheticLM(cfg)
    b1, b2 = d1.batch(7), d2.batch(7)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(d1.batch(8)["tokens"], b1["tokens"])


def test_data_host_sharding_disjoint():
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=8)
    d = SyntheticLM(cfg)
    h0 = d.batch(0, host_index=0, host_count=2)
    h1 = d.batch(0, host_index=1, host_count=2)
    assert h0["tokens"].shape[0] == 4
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_data_is_learnable_bigram():
    """Labels follow the transition table rows (next token predictable)."""
    cfg = DataConfig(vocab_size=64, seq_len=32, global_batch=2)
    d = SyntheticLM(cfg)
    b = d.batch(0)
    for row in range(2):
        for t in range(31):
            assert b["labels"][row, t] in d.table[b["tokens"][row, t]]


# ------------------------------ checkpoint ---------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    specs = {"a": P(None, None), "b": {"c": P(None)}}
    ckpt.save_checkpoint(str(tmp_path), 5, tree, specs)
    assert ckpt.latest_step(str(tmp_path)) == 5
    out = ckpt.restore_checkpoint(str(tmp_path), 5, tree)
    assert all(jnp.allclose(x, y) for x, y in
               zip(jax.tree.leaves(tree), jax.tree.leaves(out)))


def test_checkpoint_retention(tmp_path):
    tree = {"a": jnp.zeros(2)}
    for s in range(6):
        ckpt.save_checkpoint(str(tmp_path), s, tree, keep=3)
    steps = sorted(os.listdir(tmp_path))
    assert len(steps) == 3 and ckpt.latest_step(str(tmp_path)) == 5


def test_checkpoint_async(tmp_path):
    tree = {"a": jnp.arange(1000.0)}
    t = ckpt.save_checkpoint(str(tmp_path), 1, tree, async_save=True)
    t.join(timeout=30)
    out = ckpt.restore_checkpoint(str(tmp_path), 1, tree)
    assert jnp.allclose(out["a"], tree["a"])


def test_checkpoint_atomicity(tmp_path):
    """A .tmp directory from a crashed save is never treated as a step."""
    os.makedirs(tmp_path / "step_00000009.tmp")
    tree = {"a": jnp.zeros(2)}
    ckpt.save_checkpoint(str(tmp_path), 3, tree)
    assert ckpt.latest_step(str(tmp_path)) == 3


def test_checkpoint_none_specs_align_by_name(tmp_path):
    """``None`` (replicated) spec leaves must not shift the value/spec
    alignment: specs are matched by path name, not flatten order."""
    tree = {"a": jnp.arange(4.0), "b": jnp.ones((2, 2)), "c": jnp.zeros(3)}
    specs = {"a": None, "b": P(None, None), "c": P(None)}
    ckpt.save_checkpoint(str(tmp_path), 1, tree, specs)
    out = ckpt.restore_checkpoint(str(tmp_path), 1, tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_restore_uses_saved_specs(tmp_path):
    """Without a caller-supplied spec tree, restore re-resolves the logical
    specs persisted in index.json against the given mesh (host-count- and
    writer-agnostic restore)."""
    from repro.launch.mesh import make_test_mesh
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save_checkpoint(str(tmp_path), 2, tree, {"w": P("data", "model")})
    mesh = make_test_mesh(1, 1)
    out = ckpt.restore_checkpoint(str(tmp_path), 2, tree, mesh)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert out["w"].sharding.mesh.devices.size == 1


def test_moe_elastic_relayout_roundtrip():
    """(M, E_loc, D, F_loc) relayout old->new->old is the identity, for both
    the EP (E>=M) and TP-pair (E<M) regimes."""
    rng = np.random.default_rng(0)
    # EP regime: 8 experts on 4 shards -> 2 shards
    w = rng.normal(size=(4, 2, 6, 10)).astype(np.float32)
    w2 = ckpt.reshape_moe_layout(w, 4, 2, num_experts=8)
    assert w2.shape == (2, 4, 6, 10)
    back = ckpt.reshape_moe_layout(w2, 2, 4, num_experts=8)
    np.testing.assert_array_equal(back, w)
    # TP regime: 2 experts on 4 shards (tp=2) -> 2 shards (tp=1)
    w = rng.normal(size=(4, 1, 6, 5)).astype(np.float32)
    w2 = ckpt.reshape_moe_layout(w, 4, 2, num_experts=2)
    assert w2.shape == (2, 1, 6, 10)
    back = ckpt.reshape_moe_layout(w2, 2, 4, num_experts=2)
    np.testing.assert_array_equal(back, w)


# ------------------------------ resilience ---------------------------------

def test_straggler_monitor_flags_outlier():
    hits = []
    mon = StragglerMonitor(threshold=3.0,
                           on_straggler=lambda dt, med: hits.append(dt))
    for i in range(12):
        mon.step_start()
        time.sleep(0.002)
        mon.step_end()
    mon.step_start()
    time.sleep(0.05)
    assert mon.step_end() is True
    assert len(hits) == 1


def test_straggler_monitor_keeps_only_its_window():
    mon = StragglerMonitor(window=8)
    for _ in range(50):
        mon.step_start()
        mon.step_end()
    assert len(mon.durations) == 8


class _SlowLoss:
    """A loss whose read on the host blocks, as reading a device value
    waits for the step that computes it."""

    def __init__(self, seconds):
        self.seconds = seconds

    def __float__(self):
        time.sleep(self.seconds)
        return 1.0


def _drive_fake(step_once, steps, capsys):
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import _drive
    history = _drive(make_test_mesh(1, 1), start=0, steps=steps,
                     step_once=step_once, save=None,
                     log_line=lambda step, m: "", log_every=10**6,
                     ckpt_every=10**6, ckpt_dir=None)
    return history, capsys.readouterr().out


def test_drive_flags_a_step_whose_loss_read_blocks(capsys):
    """Dispatch returns at once; the slow step shows only when its loss is
    read, and ``_drive``'s straggler monitor times up to that read."""
    def step_once(step):
        return {"loss": _SlowLoss(0.15 if step == 12 else 0.002)}

    history, out = _drive_fake(step_once, 14, capsys)
    assert history == [1.0] * 14
    flagged = [ln for ln in out.splitlines() if ln.startswith("[straggler]")]
    assert len(flagged) == 1
    assert float(flagged[0].split()[3].rstrip("s")) >= 0.15


def test_drive_names_a_step_that_recompiles(capsys):
    total = jax.jit(lambda x: x.sum())

    def step_once(step):
        width = 4 if step < 3 else 5       # step 3 brings a new shape
        return {"loss": total(np.ones((width,), np.float32))}

    _, out = _drive_fake(step_once, 5, capsys)
    lines = [ln for ln in out.splitlines() if ln.startswith("[recompile]")]
    assert lines == ["[recompile] step 3: 1 executables"]


def test_elastic_plan_drops_pod_first():
    plan = ElasticPlan.after_failure((2, 16, 16), ("pod", "data", "model"),
                                     healthy_devices=256)
    assert plan.new_shape == (1, 16, 16)
    assert plan.batch_scale == 0.5


def test_elastic_plan_halves_data():
    plan = ElasticPlan.after_failure((16, 16), ("data", "model"),
                                     healthy_devices=140)
    assert plan.new_shape == (8, 16)


def test_elastic_plan_preserves_model_axis():
    with pytest.raises(RuntimeError):
        ElasticPlan.after_failure((1, 16), ("data", "model"),
                                  healthy_devices=8)


@pytest.mark.slow
def test_compressed_training_converges_like_uncompressed():
    """int8 grad compression w/ error feedback barely perturbs optimization
    on a quadratic toy problem."""
    import jax
    target = jnp.arange(1.0, 9.0)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    def run(compress):
        cfg = OptimizerConfig(lr=0.3, warmup_steps=0, total_steps=300,
                              weight_decay=0.0, compress_grads=compress)
        p = {"w": jnp.zeros(8)}
        state = init_opt_state(p, compress=compress)
        for _ in range(300):
            g = jax.grad(loss)(p)
            p, state, _ = adamw_update(p, g, state, cfg)
        return float(loss(p))

    plain, comp = run(False), run(True)
    assert plain < 1e-3
    assert comp < 0.05          # error feedback keeps the bias negligible
