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


# ------------------------------ host spans ---------------------------------

@pytest.fixture
def spans():
    from repro.analysis.tracing import reset_spans
    reset_spans()
    yield
    reset_spans()


def test_spans_count_total_and_longest(spans):
    import time

    from repro.analysis.tracing import span, span_stats
    for pause in (0.001, 0.004, 0.002):
        with span("t.step"):
            time.sleep(pause)
    s = span_stats()["t.step"]
    assert s["count"] == 3
    assert 0.007 <= s["total_s"] < 0.5
    assert 0.004 <= s["max_s"] <= s["total_s"]


def test_nested_spans_each_count_their_own_time(spans):
    import time

    from repro.analysis.tracing import span, span_stats
    with span("t.outer"):
        time.sleep(0.002)
        for _ in range(2):
            with span("t.inner"):
                time.sleep(0.003)
    s = span_stats()
    assert s["t.outer"]["count"] == 1 and s["t.inner"]["count"] == 2
    assert s["t.outer"]["total_s"] >= s["t.inner"]["total_s"] + 0.002
    assert s["t.inner"]["total_s"] >= 0.006


def test_a_span_records_when_its_block_raises(spans):
    from repro.analysis.tracing import span, span_stats
    with pytest.raises(ValueError):
        with span("t.fails"):
            raise ValueError("boom")
    assert span_stats()["t.fails"]["count"] == 1


def test_reset_and_snapshot_are_independent(spans):
    from repro.analysis.tracing import reset_spans, span, span_stats
    with span("t.once"):
        pass
    snap = span_stats()
    reset_spans()
    assert span_stats() == {}
    assert snap["t.once"]["count"] == 1


def test_data_place_span_lies_inside_its_caller_in_a_profiler_trace(
        spans, tmp_path):
    """The program's span is a profiler annotation on the caller's thread,
    on the clock of the enclosing annotation."""
    import glob

    import numpy as np
    from jax.profiler import ProfileData

    from repro.analysis.tracing import span_stats
    from repro.train.data import place_batch

    batch = {"x": np.ones((4, 8), np.float32)}
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("caller.place"):
                place_batch(batch)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {"caller.place": [], "data.place": []}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in events:
                    events[e.name].append((line.name, e.start_ns, e.end_ns))
    assert len(events["data.place"]) == 3 == len(events["caller.place"])
    for line, s, e in events["data.place"]:
        assert any(line == ln and cs <= s and e <= ce
                   for ln, cs, ce in events["caller.place"])
    assert span_stats()["data.place"]["count"] == 3


def test_compile_counter_listens_on_the_monitoring_event():
    from repro.analysis.tracing import COMPILE_EVENT
    with compile_counter() as count:
        jax.monitoring.record_event_duration_secs(COMPILE_EVENT, 0.1)
        jax.monitoring.record_event_duration_secs("/jax/other/event", 0.1)
        assert count() == 1
    jax.monitoring.record_event_duration_secs(COMPILE_EVENT, 0.1)
    assert count() == 1       # the listener is gone after the block


def test_spans_from_many_threads_lose_no_update(spans):
    import os
    import sys
    import threading

    from repro.analysis.tracing import span, span_stats
    workers, each = 2 * (os.cpu_count() or 2) + 2, 300

    def work():
        for _ in range(each):
            with span("t.threads"):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert span_stats()["t.threads"]["count"] == workers * each
