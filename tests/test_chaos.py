"""Deterministic fault injection and the guardrails that absorb it.

Unit level: schedules are seeded values that round-trip JSON; injector
hooks are exact no-ops when inactive; the in-jit non-finite guard skips a
poisoned step with state bit-identical; the kernel circuit breaker demotes
a raising impl to the jnp reference and reports it through
``describe_execution``/``audit.breaker``; checksummed checkpoints detect
corruption and ``restore_latest_good`` falls back bit-exactly; the serving
slot quarantine preserves the single-trace contract and full accounting.

End to end (the ISSUE 9 acceptance bar): one seeded mixed schedule — NaN
grad, kernel raise at a dispatch site, SIGTERM preemption, corrupted
checkpoint — replayed twice through ``repro.chaos.runner.run_chaos``
produces *identical* recovery: same events, same restarts, same loss
history, training reaches the target step both times.
"""
import math
import os
import signal
import threading

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.chaos import inject as chaos_inject
from repro.chaos.inject import (ChaosKernelFault, ChaosStepFault, activate,
                                chaos, deactivate)
from repro.chaos.schedule import SCOPES, FaultSchedule, FaultSpec


@pytest.fixture(autouse=True)
def _no_injector_leaks():
    """Every test starts and ends with no process-wide injector."""
    deactivate()
    yield
    deactivate()


@pytest.fixture(autouse=True)
def _fresh_breaker():
    from repro.core.policy import reset_breaker
    reset_breaker()
    yield
    reset_breaker()


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_generate_is_deterministic_and_covers_enabled_scopes(seed):
    kw = dict(steps=40, ckpt_every=8, kernel_sites=("pssa.qkv", "head"),
              slots=4, n_faults=6)
    a = FaultSchedule.generate(seed, **kw)
    assert a == FaultSchedule.generate(seed, **kw)
    scopes = {f.scope.split(".")[1] for f in a.faults}
    # first draws cycle every enabled kind: all five appear
    assert scopes == {"step", "grad", "ckpt", "kernel", "serving"}
    assert len(set(a.faults)) == len(a.faults), "duplicate faults survived"
    for f in a.faults:
        if f.scope in ("chaos.step", "chaos.grad", "chaos.serving.slot"):
            assert 1 <= f.step < 40


def test_schedule_json_roundtrip_and_matching(tmp_path):
    sched = FaultSchedule(seed=7, faults=(
        FaultSpec("chaos.grad", 3, "nan"),
        FaultSpec("chaos.ckpt", 4, "corrupt", mode="read"),
        FaultSpec("chaos.kernel.pssa.qkv", 0, "raise"),
    ))
    assert FaultSchedule.from_json(sched.to_json()) == sched
    p = tmp_path / "sched.json"
    sched.to_file(p)
    assert FaultSchedule.from_file(p) == sched
    assert sched.matching("chaos.grad") == (sched.faults[0],)
    assert sched.matching("chaos.step") == ()


def test_faultspec_validates_scope_action_mode():
    with pytest.raises(ValueError, match="unknown chaos scope"):
        FaultSpec("chaos.gpu", 0, "raise")
    with pytest.raises(ValueError, match="invalid for scope"):
        FaultSpec("chaos.grad", 0, "raise")
    with pytest.raises(ValueError, match="write|read"):
        FaultSpec("chaos.ckpt", 0, "corrupt", mode="sideways")
    assert SCOPES[0] == "chaos.step"


# ---------------------------------------------------------------------------
# Injector hooks
# ---------------------------------------------------------------------------

def test_hooks_are_noops_without_injector():
    batch = {"images": np.ones(3, np.float32)}
    assert chaos_inject.poison_batch(batch, 0) is batch
    chaos_inject.step_fault(0)
    chaos_inject.kernel_fault("any.site")
    logits = np.ones((2, 4))
    assert chaos_inject.serving_fault(logits, 0) is logits
    assert chaos_inject.activate_from_env({}) is None


def test_poison_batch_hits_first_float_leaf_and_records():
    inj = activate(FaultSchedule(faults=(
        FaultSpec("chaos.grad", 2, "nan"),)))
    batch = {"labels": np.arange(4), "images": np.ones((2, 2), np.float32)}
    same = chaos_inject.poison_batch(batch, 1)
    assert same is batch                     # wrong step: untouched
    out = chaos_inject.poison_batch(batch, 2)
    assert np.isnan(out["images"]).sum() == 1
    assert np.all(np.isfinite(batch["images"])), "input batch mutated"
    assert np.array_equal(out["labels"], batch["labels"])
    assert inj.events == ["chaos.grad@2:nan leaf=images"]
    # data-dependent fault: re-fires on replay of the same step
    again = chaos_inject.poison_batch(batch, 2)
    assert np.isnan(again["images"]).sum() == 1


def test_step_raise_and_sigterm_are_one_shot():
    activate(FaultSchedule(faults=(FaultSpec("chaos.step", 3, "raise"),)))
    with pytest.raises(ChaosStepFault):
        chaos_inject.step_fault(3)
    chaos_inject.step_fault(3)               # replay after restart: no refire

    # sigterm delivers a real signal exactly once
    got = []
    prev = signal.signal(signal.SIGTERM, lambda *_: got.append(1))
    try:
        activate(FaultSchedule(faults=(
            FaultSpec("chaos.step", 1, "sigterm"),)))
        chaos_inject.step_fault(1)
        chaos_inject.step_fault(1)
        assert got == [1]
    finally:
        signal.signal(signal.SIGTERM, prev)


# ---------------------------------------------------------------------------
# Non-finite guard
# ---------------------------------------------------------------------------

def test_nonfinite_guard_budget_counts_consecutive_only():
    from repro.train.resilience import NonFiniteBudgetExceeded, NonFiniteGuard
    g = NonFiniteGuard(budget=2)
    assert not g.observe(False, 0)
    assert g.observe(True, 1) and g.observe(True, 2)
    assert not g.observe(False, 3)           # streak broken: budget resets
    g.observe(True, 4)
    g.observe(True, 5)
    with pytest.raises(NonFiniteBudgetExceeded):
        g.observe(True, 6)
    assert g.skipped_steps == [1, 2, 4, 5, 6]


def test_injit_guard_skips_step_with_state_bit_identical():
    """A poisoned batch must leave params/opt/BN-state bit-identical and
    flag ``metrics['nonfinite']``; a clean batch must train normally."""
    import jax
    from repro.configs.spikingformer import get_spikingformer_config
    from repro.train.loop import make_train_step
    from repro.train.optimizer import OptimizerConfig, init_opt_state

    cfg = get_spikingformer_config("spikingformer-smoke")
    from repro.core.spikingformer import init_spikingformer
    params, state = init_spikingformer(jax.random.PRNGKey(0), cfg)
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(
        cfg, OptimizerConfig(lr=1e-3, total_steps=10), 1))
    imgs = np.random.default_rng(0).normal(
        size=(2, cfg.image_size, cfg.image_size,
              cfg.in_channels)).astype(np.float32)
    labels = np.zeros(2, np.int64)
    bad = imgs.copy()
    bad[0].reshape(-1)[0] = np.nan

    p1, s1, o1, m1 = step(params, state, opt, bad, labels)
    assert float(m1["nonfinite"]) == 1.0
    for a, b in zip(jax.tree.leaves((params, state, opt)),
                    jax.tree.leaves((p1, s1, o1))):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "guarded step must leave state bit-identical"

    p2, s2, o2, m2 = step(params, state, opt, imgs, labels)
    assert float(m2["nonfinite"]) == 0.0
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))


# ---------------------------------------------------------------------------
# Kernel circuit breaker
# ---------------------------------------------------------------------------

def test_circuit_breaker_demotes_once_and_reports():
    from repro.analysis.audit import audit_breaker
    from repro.core.policy import (breaker_trips, describe_breaker,
                                   dispatch_site, reset_breaker)

    calls = {"impl": 0, "ref": 0}

    def broken():
        calls["impl"] += 1
        raise FloatingPointError("bad kernel")

    def ref():
        calls["ref"] += 1
        return "ref-result"

    out1 = dispatch_site("pssa.qkv", "attn_qk", "pallas", broken,
                         fallback_impl="jnp", fallback_invoke=ref)
    out2 = dispatch_site("pssa.qkv", "attn_qk", "pallas", broken,
                         fallback_impl="jnp", fallback_invoke=ref)
    assert out1 == out2 == "ref-result"
    assert calls == {"impl": 1, "ref": 2}, \
        "tripped site must not re-run the broken impl"
    trips = breaker_trips()
    assert set(trips) == {"pssa.qkv"}
    assert trips["pssa.qkv"].fallback == "jnp"
    assert "FloatingPointError" in trips["pssa.qkv"].error
    assert "pssa.qkv" in describe_breaker()
    findings = audit_breaker()
    assert [f.check for f in findings] == ["audit.breaker"]
    assert findings[0].level == "warning"
    reset_breaker()
    assert breaker_trips() == {} and describe_breaker() == ""


def test_breaker_propagates_when_no_fallback_exists():
    from repro.core.policy import breaker_trips, dispatch_site

    def broken():
        raise FloatingPointError("bad kernel")

    # impl == fallback (already the reference): nothing to demote to.
    with pytest.raises(FloatingPointError):
        dispatch_site("site.x", "op", "jnp", broken,
                      fallback_impl="jnp", fallback_invoke=lambda: "r")
    assert breaker_trips() == {}


def test_chaos_kernel_fault_trips_breaker_in_model_dispatch():
    """An injected ``chaos.kernel.<site>`` fault inside real model dispatch
    demotes that site and shows up in ``describe_execution``."""
    import jax
    from repro.configs.spikingformer import get_spikingformer_config
    from repro.core.policy import breaker_trips, named_policy
    from repro.core.spikingformer import init_spikingformer, spikingformer_apply

    cfg = get_spikingformer_config("spikingformer-smoke",
                                   policy=named_policy("pallas"))
    with chaos(FaultSchedule(faults=(
            FaultSpec("chaos.kernel.pssa.qkv", 0, "raise"),))) as inj:
        params, state = init_spikingformer(jax.random.PRNGKey(0), cfg)
        x = np.zeros((1, cfg.image_size, cfg.image_size, cfg.in_channels),
                     np.float32)
        logits, _ = spikingformer_apply(params, state, x, cfg, train=False)
        assert np.all(np.isfinite(np.asarray(logits)))
        assert inj.events == ["chaos.kernel.pssa.qkv@0:raise"]
    assert set(breaker_trips()) == {"pssa.qkv"}
    assert "pssa.qkv" in cfg.describe_execution()


# ---------------------------------------------------------------------------
# Checkpoint integrity
# ---------------------------------------------------------------------------

def _tree(step):
    return {"w": np.full((4, 3), float(step), np.float32),
            "b": np.arange(6, dtype=np.float32) + step}


def test_restore_falls_back_past_corruption_bit_exactly(tmp_path):
    from repro.train import checkpoint as ckpt
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 2, _tree(2))
    ckpt.save_checkpoint(d, 4, _tree(4))
    assert ckpt.verify_checkpoint(d, 4) == []

    # flip one payload byte of one leaf of the newest step
    victim = os.path.join(d, "step_00000004", "w.npy")
    with open(victim, "r+b") as f:
        f.seek(os.path.getsize(victim) - 3)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0xFF]))
    assert ckpt.verify_checkpoint(d, 4) == ["w"]
    with pytest.raises(ckpt.CheckpointCorruptError, match="CRC mismatch"):
        ckpt.restore_checkpoint(d, 4, _tree(0))

    with pytest.warns(RuntimeWarning, match="falling back"):
        step, tree = ckpt.restore_latest_good(d, _tree(0))
    assert step == 2
    for k in ("w", "b"):
        assert np.array_equal(np.asarray(tree[k]), _tree(2)[k]), \
            "fallback restore must be bit-exact"


def test_restore_falls_back_past_truncation_and_sweeps_tmp(tmp_path):
    from repro.train import checkpoint as ckpt
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 1, _tree(1))
    ckpt.save_checkpoint(d, 2, _tree(2))
    victim = os.path.join(d, "step_00000002", "b.npy")
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) // 2)
    os.makedirs(os.path.join(d, "step_00000009.tmp"))   # dead writer debris
    with pytest.warns(RuntimeWarning):
        step, tree = ckpt.restore_latest_good(d, _tree(0))
    assert step == 1 and np.array_equal(np.asarray(tree["w"]), _tree(1)["w"])
    assert not os.path.exists(os.path.join(d, "step_00000009.tmp"))
    assert ckpt.latest_step(d) == 2      # corrupt step left for forensics


def test_restore_latest_good_empty_dir_starts_fresh(tmp_path):
    from repro.train import checkpoint as ckpt
    assert ckpt.restore_latest_good(str(tmp_path / "nope"), _tree(0)) == \
        (None, None)


def test_chaos_ckpt_write_fault_is_caught_by_verify(tmp_path):
    from repro.train import checkpoint as ckpt
    d = str(tmp_path)
    with chaos(FaultSchedule(seed=5, faults=(
            FaultSpec("chaos.ckpt", 2, "corrupt", mode="write"),))) as inj:
        ckpt.save_checkpoint(d, 2, _tree(2))
        assert len(inj.events) == 1 and "corrupt" in inj.events[0]
    bad = ckpt.verify_checkpoint(d, 2)
    assert len(bad) == 1, f"one leaf must fail its CRC, got {bad}"


def test_drive_raises_when_final_writer_hangs():
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import _drive
    from repro.train import checkpoint as ckpt

    class HungWriter:
        def join(self, timeout=None):
            pass

        def is_alive(self):
            return True

    with pytest.raises(ckpt.CheckpointWriteTimeout, match="still running"):
        _drive(make_test_mesh(1, 1), start=0, steps=1,
               step_once=lambda s: {"loss": 0.0},
               save=lambda s: HungWriter(),
               log_line=lambda s, m: f"step {s}", log_every=1,
               ckpt_every=1, ckpt_dir="/tmp/ignored",
               final_join_timeout=0.01)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_async_save_remains_atomic_under_mid_write_kill(tmp_path):
    """A writer killed between leaf writes must leave no half-published
    step: the interrupted write stays a ``.tmp`` that restore sweeps."""
    from repro.train import checkpoint as ckpt
    d = str(tmp_path)
    ckpt.save_checkpoint(d, 1, _tree(1))

    release = threading.Event()
    orig_fsync_write = ckpt._fsync_write
    calls = {"n": 0}

    def stalling(path, write_fn):
        calls["n"] += 1
        if calls["n"] == 2:
            release.wait(timeout=10)
            raise OSError("simulated writer death mid-step")
        orig_fsync_write(path, write_fn)

    ckpt._fsync_write = stalling
    try:
        t = ckpt.save_checkpoint(d, 3, _tree(3), async_save=True)
        release.set()
        t.join(timeout=10)
    finally:
        ckpt._fsync_write = orig_fsync_write
    assert ckpt.latest_step(d) == 1, "half-written step must not publish"
    step, tree = ckpt.restore_latest_good(d, _tree(0))
    assert step == 1 and np.array_equal(np.asarray(tree["b"]), _tree(1)["b"])


# ---------------------------------------------------------------------------
# Serving slot quarantine
# ---------------------------------------------------------------------------

def test_serving_quarantine_keeps_single_trace_and_accounting():
    import jax
    from repro.analysis.tracing import assert_trace_count
    from repro.configs.registry import get_config, reduced
    from repro.models.common import split_tree
    from repro.models.lm import init_lm
    from repro.serving.engine import ServingEngine
    from repro.serving.scheduler import Request

    cfg = reduced(get_config("qwen3-0.6b"))
    params = split_tree(init_lm(jax.random.PRNGKey(0), cfg))[0]
    engine = ServingEngine(params, cfg, slots=2, max_seq=32)
    reqs = [Request(uid=i, prompt=[5 + i, 7], max_new_tokens=6)
            for i in range(4)]
    with chaos(FaultSchedule(faults=(
            FaultSpec("chaos.serving.slot", 3, "nan", value=0.0),))) as inj:
        for r in reqs:
            engine.submit(r)
        engine.run_to_completion(max_steps=100)
        assert any(e.startswith("chaos.serving.slot@3:nan") for e in
                   inj.events)
    assert len(engine.faulted) == 1
    bad = engine.faulted[0]
    assert bad.status == "faulted" and bad.reason == "numeric_fault"
    assert len(engine.finished) == 3
    assert len(engine.finished) + len(engine.faulted) == len(reqs)
    for r in engine.finished:
        assert len(r.output) == r.max_new_tokens
        assert all(t >= 0 for t in r.output)
    # the quarantine flush must not have re-traced the fused step
    assert_trace_count(1, engine._step)
    # the faulted slot was reused cleanly by a later admission
    assert engine.sched.free_slots() == [0, 1]


# ---------------------------------------------------------------------------
# End to end: the acceptance replay
# ---------------------------------------------------------------------------

#: NaN grad at step 3, kernel raise at the first pssa.qkv dispatch, SIGTERM
#: preemption at step 5, and the step-6 checkpoint corrupted right after its
#: atomic publish — so the post-preemption restart must fall back to step 4.
ACCEPTANCE_SCHEDULE = FaultSchedule(seed=9, faults=(
    FaultSpec("chaos.grad", 3, "nan"),
    FaultSpec("chaos.kernel.pssa.qkv", 0, "raise"),
    FaultSpec("chaos.step", 5, "sigterm"),
    FaultSpec("chaos.ckpt", 6, "corrupt", mode="write"),
))


def _acceptance_run(tmp_path, tag):
    """One full chaos run through the real CLI in a subprocess.

    A subprocess, not in-process ``run_chaos``: the restart loop compiles
    the train step, then recompiles the identical step after restore, and
    on this jaxlib any prior *serialization* into the persistent
    compilation cache (which the conftest enables for the rest of the
    suite) leaves the process heap in a state that recompile aborts on —
    the same native-code bug family the conftest documents for
    multi-device deserialization. The CLI is also exactly what the CI
    chaos leg runs."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    # Replay determinism requires a fixed device topology: in a full-suite
    # run test_distributed's import has already forced an 8-device host
    # into os.environ, which the drill must not inherit.
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    sched = tmp_path / "sched.json"
    ACCEPTANCE_SCHEDULE.to_file(sched)
    report_path = tmp_path / f"{tag}.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.chaos.runner", "--steps", "8",
         "--ckpt-every", "2", "--batch", "2", "--seed", "9",
         "--policy", "pallas", "--schedule", str(sched),
         "--ckpt-dir", str(tmp_path / tag),
         "--report-out", str(report_path)],
        capture_output=True, text=True, env=env, cwd=repo, timeout=600)
    assert proc.returncode == 0, \
        f"chaos runner failed:\n{proc.stdout}\n{proc.stderr}"
    import json
    return json.loads(report_path.read_text()), proc.stdout + proc.stderr


def test_e2e_mixed_fault_replay_recovers_identically(tmp_path):
    from repro.train import checkpoint as ckpt

    r1, out1 = _acceptance_run(tmp_path, "run1")
    r2, out2 = _acceptance_run(tmp_path, "run2")

    for r, out in ((r1, out1), (r2, out2)):
        assert r["completed"] and r["final_step"] == 8 and r["final_ckpt_ok"]
        # preemption at step 5 forced exactly one restart
        assert r["restarts"] == 1
        assert r["breaker_sites"] == ["pssa.qkv"]
        # every scheduled fault actually fired
        fired = {e.split("@")[0] for e in r["events"]}
        assert fired == {"chaos.grad", "chaos.kernel.pssa.qkv",
                         "chaos.step", "chaos.ckpt"}
        # recovery visible in the log: breaker demotion, guard skip,
        # preemption save, corrupt-checkpoint fallback
        assert "demoted to 'jnp'" in out
        assert "non-finite loss/grads" in out
        assert "[preempt] checkpoint saved" in out
        assert "falling back to the previous retained step" in out
        assert "clean recovery" in out

    # identical recovery, replay for replay: same events, same loss
    # trajectory (the poisoned step's non-finite loss included — compare
    # with NaN equality), same restart count.
    assert r1["events"] == r2["events"]
    # history covers the final (resumed) attempt: steps 4..7
    assert len(r1["history"]) == len(r2["history"]) == 4
    for a, b in zip(r1["history"], r2["history"]):
        assert (math.isnan(a) and math.isnan(b)) or a == b
    # the corrupted step 6 was re-written by the restarted run: every
    # retained checkpoint in both dirs now verifies clean
    for tag in ("run1", "run2"):
        d = str(tmp_path / tag)
        assert ckpt.retained_steps(d), "no checkpoints retained"
        for step in ckpt.retained_steps(d):
            assert ckpt.verify_checkpoint(d, step) == []
