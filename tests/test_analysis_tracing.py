"""Tests for the trace-count guard (repro.analysis.tracing): the guard
itself (per-function and global forms), and the two hot paths it exists
to protect — the vision train step and the serving decode step — pinned
to their planned compile counts."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.tracing import (assert_trace_count, compile_counter,
                                    trace_count)

KEY = jax.random.PRNGKey(0)


def test_trace_count_counts_per_shape_traces():
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones((2,)))
    f(jnp.ones((2,)))
    assert trace_count(f) == 1
    f(jnp.ones((3,)))
    assert trace_count(f) == 2


def test_guard_passes_on_single_trace():
    f = jax.jit(lambda x: x + 1)
    with assert_trace_count(1, f):
        for _ in range(3):
            f(jnp.ones((4,)))


def test_guard_fails_on_retrace():
    f = jax.jit(lambda x: x + 1)
    with pytest.raises(AssertionError, match="retrace"):
        with assert_trace_count(1, f):
            f(jnp.ones((4,)))
            f(jnp.ones((5,)))   # new shape: second trace


def test_guard_at_most_allows_fewer():
    f = jax.jit(lambda x: x - 1)
    with assert_trace_count(2, f, exact=False):
        f(jnp.ones((4,)))


def test_global_compile_counter_counts_block_compiles():
    with compile_counter() as count:
        g = jax.jit(lambda x: x * 3)
        g(jnp.ones((4,)))
        g(jnp.ones((4,)))
        compiled = count()
    assert compiled == 1


def test_global_guard_form_covers_inner_jits():
    with assert_trace_count(1):
        jax.jit(lambda x: x / 3)(jnp.ones((2,)))
    with pytest.raises(AssertionError, match="retrace"):
        with assert_trace_count(1):
            h = jax.jit(lambda x: x / 4)
            h(jnp.ones((2,)))
            h(jnp.ones((3,)))


def test_train_step_is_single_trace():
    """make_train_step's product must hold one trace across same-shape
    steps — the policy rides the config as a hashable static."""
    from repro.configs.spikingformer import get_spikingformer_config
    from repro.core.policy import named_policy
    from repro.core.spikingformer import init_spikingformer
    from repro.train.loop import make_train_step
    from repro.train.optimizer import OptimizerConfig, init_opt_state

    cfg = get_spikingformer_config("spikingformer-smoke",
                                   policy=named_policy("jnp"))
    params, state = init_spikingformer(KEY, cfg)
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=4)
    step = jax.jit(make_train_step(cfg, opt_cfg))
    opt_state = init_opt_state(params)
    images = jnp.zeros((2, cfg.image_size, cfg.image_size, 3))
    labels = jnp.arange(2) % cfg.num_classes
    with assert_trace_count(1, step):
        for _ in range(2):
            params, state, opt_state, _ = step(params, state, opt_state,
                                               images, labels)


def test_serving_engine_step_is_single_trace():
    from repro.configs.registry import get_config, reduced
    from repro.models.common import split_tree
    from repro.models.lm import init_lm
    from repro.serving.engine import Request, ServingEngine

    cfg = reduced(get_config("qwen3-0.6b"))
    params = split_tree(init_lm(KEY, cfg))[0]
    engine = ServingEngine(params, cfg, slots=2, max_seq=32)
    assert engine.submit(Request(uid=0, prompt=[3, 1, 2], max_new_tokens=4))
    assert engine.submit(Request(uid=1, prompt=[5], max_new_tokens=3))
    with assert_trace_count(1, engine._step, exact=False):
        done = engine.run_to_completion()
    assert sorted(r.uid for r in done) == [0, 1]
    assert engine.trace_count() == 1
