"""Continuous-batching serving: the slot-level parity + fault suite.

Contracts proven here:

* **Parity.** Continuous-batched greedy decode of N interleaved requests is
  token-for-token identical to serving each request alone — including
  requests admitted mid-flight into a slot another request just vacated
  (the slot-state-leak test) — for dense, recurrent (RWKV) and spiking
  (``cfg.lif``, the persistent (U, S) neuron-state cache) LMs. "Identical"
  is checked via the teacher-forced solo oracle of ``_serving_parity``
  (argmax up to float-tie tolerance), because free-running greedy equality
  on random weights flips on knife-edge logit ties.
* **Single trace.** One fused jit'd step serves admits, prefill and
  generation across a whole mixed workload.
* **Reset = init.** ``reset_cache_slots`` reproduces ``init_cache`` exactly
  per slot (the masked-zero-fill premise) for every cache family.
* **Faults.** Over-capacity and over-length submits are rejected explicitly;
  evicting a mid-prefill request resets its slot state to init; deadlines
  expire with partial output while the queue keeps draining.
* **The wave-engine regression.** A skewed workload costs ~the sum of
  per-request steps in occupied slot-steps, not slots x max like the old
  wave engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _serving_parity import assert_greedy_parity
from repro.configs.registry import get_config, reduced
from repro.core.lif import LIFConfig
from repro.core.policy import ExecutionPolicy
from repro.models.common import split_tree, unembed
from repro.models.lm import (cache_batch_axes, init_cache, init_lm,
                             lm_decode_step, lm_forward, reset_cache_slots)
from repro.serving.engine import Request, ServingEngine

KEY = jax.random.PRNGKey(0)

_PARAMS: dict = {}


def _cfg(name: str, spiking: bool = False):
    cfg = reduced(get_config(name))
    return cfg.replace(lif=LIFConfig()) if spiking else cfg


def _params(cfg):
    if cfg not in _PARAMS:
        _PARAMS[cfg] = split_tree(init_lm(KEY, cfg))[0]
    return _PARAMS[cfg]


PROMPTS = [[3, 17, 42], [5, 9], [100, 7, 3], [8], [12, 13, 14, 15]]
BUDGETS = [5, 4, 6, 3, 4]


# ---------------------------------------------------------------------------
# Parity: continuous == solo, token for token
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,spiking", [
    ("qwen3-0.6b", False),
    ("qwen3-0.6b", True),       # dense + LIF (U, S) neuron-state cache
    ("rwkv6-7b", True),         # recurrent state + LIF carry
])
def test_continuous_matches_solo(name, spiking):
    """5 requests through 2 slots: at least 3 admissions land in slots a
    previous request vacated mid-flight; every output must equal the solo
    greedy decode bit for bit."""
    cfg = _cfg(name, spiking)
    params = _params(cfg)
    engine = ServingEngine(params, cfg, slots=2, max_seq=64)
    for uid, (p, b) in enumerate(zip(PROMPTS, BUDGETS)):
        assert engine.submit(Request(uid=uid, prompt=p, max_new_tokens=b))
    done = engine.run_to_completion()
    assert sorted(r.uid for r in done) == list(range(5))
    for r in done:
        assert_greedy_parity(params, cfg, r)
    assert engine.trace_count() == 1   # the single-trace contract


def test_admit_mid_flight_into_vacated_slot():
    """The slot-state-leak test: C is admitted into the slot B just vacated
    while A is still generating; C must decode as if the slot were fresh."""
    cfg = _cfg("qwen3-0.6b", spiking=True)
    params = _params(cfg)
    engine = ServingEngine(params, cfg, slots=2, max_seq=64)
    a = Request(uid=0, prompt=[7, 3, 9], max_new_tokens=12)
    b = Request(uid=1, prompt=[100, 7], max_new_tokens=2)
    engine.submit(a)
    engine.submit(b)
    while not engine.finished:          # run until B (the short one) drains
        engine.step()
    assert engine.finished[0].uid == 1
    assert a.status == "running"        # A still mid-flight
    c = Request(uid=2, prompt=[5, 9], max_new_tokens=4)
    engine.submit(c)
    engine.run_to_completion()
    assert c.admit_step > b.finish_step - 1     # reused a vacated slot
    for r in (a, b, c):
        assert_greedy_parity(params, cfg, r)


@pytest.mark.parametrize("name", ["qwen3-0.6b", "rwkv6-7b", "zamba2-2.7b"])
def test_spiking_decode_matches_forward(name):
    """The (U, S) cache continues the training-time sequence-as-time LIF
    recursion: token-by-token decode logits == full-sequence forward."""
    cfg = _cfg(name, spiking=True)
    params = _params(cfg)
    toks = np.array([[3, 7, 11, 2, 5]], np.int32)
    x, _ = lm_forward(params, {"tokens": jnp.asarray(toks)}, cfg)
    logits_fwd = np.asarray(unembed(params["embed"], x))[0]
    cache = init_cache(cfg, 1, 32, jnp.float32)
    for t in range(toks.shape[1]):
        lg, cache = lm_decode_step(params, cache,
                                   jnp.asarray(toks[:, t:t + 1]),
                                   jnp.asarray([t], jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(lg)[0], logits_fwd[t],
                                   atol=1e-5, rtol=1e-5)


def test_lif_decode_step_pallas_parity():
    """The serving step's fused carry kernel (ops.lif_soma_step_op via a
    pallas-backed policy) matches the pure jnp SOMA step exactly."""
    from repro.core.lif import lif_decode_step, lif_step
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (4, 64), jnp.float32) * 2.0
    u0 = jax.random.normal(k2, (4, 64), jnp.float32)
    s0 = (jax.random.uniform(k3, (4, 64)) > 0.5).astype(jnp.float32)
    jnp_cfg = LIFConfig()
    pl_cfg = LIFConfig(policy=ExecutionPolicy(backend="pallas"))
    s_ref, (u_ref, ss_ref) = lif_decode_step(x, u0, s0, jnp_cfg)
    s_pl, (u_pl, ss_pl) = lif_decode_step(x, u0, s0, pl_cfg)
    np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_pl))
    np.testing.assert_allclose(np.asarray(u_ref), np.asarray(u_pl), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ss_ref), np.asarray(ss_pl))


# ---------------------------------------------------------------------------
# Reset = init (the masked-zero-fill premise, per cache family)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,spiking", [
    ("qwen3-0.6b", True),        # dense KV + lif
    ("deepseek-v2-236b", False),  # MLA latent cache
    ("mixtral-8x7b", False),     # sliding-window ring buffer
    ("rwkv6-7b", True),          # rwkv recurrences + lif
    ("zamba2-2.7b", True),       # hybrid: grouped mamba + shared KV
])
def test_reset_cache_slots_matches_init(name, spiking):
    cfg = _cfg(name, spiking)
    init = init_cache(cfg, 3, 16, jnp.float32)
    dirty = jax.tree.map(lambda a: jnp.full_like(a, 7.0), init)
    # Full reset reproduces init exactly on every leaf...
    full = reset_cache_slots(dirty, jnp.array([True] * 3), cfg)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), full, init)
    # ...and a slot-1-only reset leaves slots 0/2 untouched.
    part = reset_cache_slots(dirty, jnp.array([False, True, False]), cfg)
    axes = cache_batch_axes(cfg, part)

    def check(a, ax):
        a = np.moveaxis(np.asarray(a), ax, 0)
        assert (a[1] == 0).all()
        assert (a[0] == 7.0).all() and (a[2] == 7.0).all()
    jax.tree.map(check, part, axes)


# ---------------------------------------------------------------------------
# Faults: explicit rejection, eviction reset, deadlines
# ---------------------------------------------------------------------------

def test_over_capacity_rejection_is_explicit():
    cfg = _cfg("qwen3-0.6b")
    engine = ServingEngine(_params(cfg), cfg, slots=1, max_seq=64,
                           max_queue=2)
    reqs = [Request(uid=i, prompt=[1, 2], max_new_tokens=2)
            for i in range(5)]
    oks = [engine.submit(r) for r in reqs]
    assert oks == [True, True, False, False, False]
    assert all(r.status == "rejected" and r.reason == "queue_full"
               for r in reqs[2:])
    done = engine.run_to_completion()
    # Full accounting: nothing dropped silently.
    assert {r.uid for r in done} | {r.uid for r in engine.rejected} \
        == set(range(5))


def test_over_length_rejection_is_explicit():
    cfg = _cfg("qwen3-0.6b")
    engine = ServingEngine(_params(cfg), cfg, slots=1, max_seq=16)
    bad = Request(uid=0, prompt=[1] * 10, max_new_tokens=10)
    assert not engine.submit(bad)
    assert bad.status == "rejected" and bad.reason == "too_long"
    assert engine.rejected == [bad]


def test_evict_mid_prefill_resets_slot_state():
    """Evicting a request mid-prefill must return its slot to the init
    state (all-zeros) immediately — and the next occupant decodes as if
    the slot were fresh."""
    cfg = _cfg("qwen3-0.6b", spiking=True)
    params = _params(cfg)
    engine = ServingEngine(params, cfg, slots=2, max_seq=64)
    a = Request(uid=0, prompt=list(range(1, 9)), max_new_tokens=4)
    b = Request(uid=1, prompt=[2, 3], max_new_tokens=3)
    engine.submit(a)
    engine.submit(b)
    engine.step()
    engine.step()                       # A is mid-prefill (8-token prompt)
    assert a.status == "running" and not a.output
    assert engine.evict(0) is a
    assert a.status == "evicted"
    state = engine.slot_state(0)        # flushes the reset first
    jax.tree.map(lambda leaf: np.testing.assert_array_equal(
        np.asarray(leaf), 0.0), state)
    c = Request(uid=2, prompt=[5, 9], max_new_tokens=4)
    engine.submit(c)
    engine.run_to_completion()
    for r in (b, c):
        assert_greedy_parity(params, cfg, r)


def test_deadline_expires_with_partial_output():
    cfg = _cfg("qwen3-0.6b")
    params = _params(cfg)
    engine = ServingEngine(params, cfg, slots=1, max_seq=64)
    a = Request(uid=0, prompt=[3, 4], max_new_tokens=30, deadline=6)
    b = Request(uid=1, prompt=[5, 6], max_new_tokens=3)
    engine.submit(a)
    engine.submit(b)
    engine.run_to_completion()
    assert a.status == "expired" and a.reason == "deadline"
    assert 0 < len(a.output) < 30       # partial output is preserved
    assert b.status == "done"
    assert_greedy_parity(params, cfg, b)


# ---------------------------------------------------------------------------
# The wave-engine drained-slot-waste regression
# ---------------------------------------------------------------------------

def test_skewed_workload_slot_steps_near_optimal():
    """One 200-token request + seven 5-token requests: occupied slot-steps
    must stay within 1.2x the sum of per-request steps. The old wave engine
    kept all 8 slots stepping until the 200-token request drained — ~8x the
    longest request, ~6.6x the useful work."""
    cfg = _cfg("qwen3-0.6b")
    engine = ServingEngine(_params(cfg), cfg, slots=8, max_seq=256)
    reqs = [Request(uid=0, prompt=[1, 2], max_new_tokens=200)]
    reqs += [Request(uid=i, prompt=[i, i + 1], max_new_tokens=5)
             for i in range(1, 8)]
    for r in reqs:
        engine.submit(r)
    done = engine.run_to_completion(max_steps=1000)
    assert len(done) == 8
    per_request = sum(len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
    assert engine.active_slot_steps <= 1.2 * per_request
    # The wave engine's cost model for the same workload:
    wave_cost = engine.slots * max(len(r.prompt) + r.max_new_tokens - 1
                                   for r in reqs)
    assert wave_cost >= 5 * engine.active_slot_steps
    # And wall-steps track the longest request, not the sum:
    assert engine.step_count <= 202
