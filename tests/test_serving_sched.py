"""Property-based scheduler tests for continuous-batching serving.

Random arrival orders, prompt lengths, token budgets and deadlines through
:class:`repro.serving.scheduler.FIFOScheduler` (pure-python simulation, no
model) and through the real :class:`ServingEngine` (tiny model) must:

* never deadlock — the system drains in a bounded number of steps;
* never drop a request silently — every submit ends in exactly one terminal
  status (done/expired/evicted/faulted) or an explicit rejection with a
  reason;
* never double-book a slot — slot occupants are unique, and misuse raises
  :class:`SlotError` rather than corrupting a neighbour;
* admit in FIFO order;
* keep all of the above when the fused launch itself raises mid-drain
  (failure-atomic steps) or a slot produces non-finite logits (quarantine).
"""
import random

import pytest

from hypothesis import given, settings, strategies as st

from repro.serving.scheduler import FIFOScheduler, Request, SlotError

TERMINAL = {"done", "expired", "evicted", "rejected"}


def _simulate(seed: int, slots: int, n_requests: int,
              max_queue: int | None):
    """Drive the scheduler the way the engine does: one loop iteration ==
    one engine step; each running request consumes one unit of work
    (prefill token or generated token) per step."""
    rng = random.Random(seed)
    reqs = [Request(uid=i, prompt=[1] * rng.randint(1, 6),
                    max_new_tokens=rng.randint(1, 5),
                    deadline=rng.choice([None, None, rng.randint(1, 40)]))
            for i in range(n_requests)]
    arrivals: dict[int, list[Request]] = {}
    for r in reqs:
        arrivals.setdefault(rng.randint(0, 10), []).append(r)
    last_arrival = max(arrivals)

    sched = FIFOScheduler(slots, max_queue)
    accepted, rejected, expired, finished = [], [], [], []
    work: dict[int, int] = {}
    admit_order: list[int] = []
    t = 0
    while t <= last_arrival or sched.has_work():
        assert t < 1000, "deadlock: scheduler failed to drain"
        for r in arrivals.get(t, []):
            (accepted if sched.submit(r, t) else rejected).append(r)
        eq, er = sched.expire(t)
        expired.extend(eq)
        expired.extend(r for _, r in er)
        for slot, req in sched.admit(t):
            assert sched.slot_map[slot] is req
            work[req.uid] = len(req.prompt) - 1 + req.max_new_tokens
            admit_order.append(req.uid)
        live = [r.uid for r in sched.slot_map if r is not None]
        assert len(live) == len(set(live)), "slot double-booked"
        for slot in range(slots):
            req = sched.slot_map[slot]
            if req is None:
                continue
            work[req.uid] -= 1
            if work[req.uid] <= 0:
                assert sched.release(slot) is req
                req.status, req.done, req.finish_step = "done", True, t
                finished.append(req)
        t += 1
    return reqs, accepted, rejected, expired, finished, admit_order


@given(seed=st.integers(0, 10_000), slots=st.integers(1, 4),
       n=st.integers(1, 14), cap=st.sampled_from([None, 1, 3]))
@settings(max_examples=40, deadline=None)
def test_random_workloads_drain_without_loss(seed, slots, n, cap):
    reqs, accepted, rejected, expired, finished, admit_order = \
        _simulate(seed, slots, n, cap)
    # Never silently dropped: full accounting, each request exactly once.
    assert len(accepted) + len(rejected) == len(reqs)
    terminal = {r.uid for r in finished} | {r.uid for r in expired} \
        | {r.uid for r in rejected}
    assert terminal == {r.uid for r in reqs}
    assert len(finished) + len(expired) + len(rejected) == len(reqs)
    for r in reqs:
        assert r.status in TERMINAL, f"uid {r.uid} left in {r.status!r}"
    # Rejections only ever happen for a stated reason at capacity.
    for r in rejected:
        assert cap is not None and r.reason == "queue_full"
    # FIFO: admissions respect (submit_step, uid-submission) order.
    keyed = sorted(admit_order,
                   key=lambda u: (reqs[u].submit_step,
                                  admit_order.index(u)))
    assert all(reqs[u].admit_step >= reqs[u].submit_step
               for u in admit_order)
    assert keyed == admit_order


@given(seed=st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_fifo_admission_order_within_step(seed):
    """Requests submitted in one step are admitted in submission order."""
    rng = random.Random(seed)
    sched = FIFOScheduler(slots=rng.randint(1, 3))
    reqs = [Request(uid=i, prompt=[1], max_new_tokens=1) for i in range(6)]
    for r in reqs:
        sched.submit(r, 0)
    seen = []
    t = 0
    while sched.has_work():
        for slot, req in sched.admit(t):
            seen.append(req.uid)
        for i, r in enumerate(sched.slot_map):
            if r is not None:
                sched.release(i)
        t += 1
    assert seen == [0, 1, 2, 3, 4, 5]


def test_release_free_slot_raises():
    sched = FIFOScheduler(slots=2)
    with pytest.raises(SlotError):
        sched.release(0)
    sched.submit(Request(uid=0, prompt=[1]), 0)
    [(slot, _)] = sched.admit(0)
    sched.release(slot)
    with pytest.raises(SlotError):       # double-free
        sched.release(slot)


def test_admit_never_overfills():
    sched = FIFOScheduler(slots=2)
    for i in range(5):
        sched.submit(Request(uid=i, prompt=[1]), 0)
    admitted = sched.admit(0)
    assert [s for s, _ in admitted] == [0, 1]
    assert sched.admit(0) == []          # no free slots -> no-op, no error
    assert len(sched.queue) == 3


def test_queue_capacity_is_exact():
    sched = FIFOScheduler(slots=1, max_queue=2)
    results = [sched.submit(Request(uid=i, prompt=[1]), 0) for i in range(4)]
    assert results == [True, True, False, False]
    sched.admit(0)                       # frees a queue seat
    assert sched.submit(Request(uid=9, prompt=[1]), 1)


def test_deadline_expires_queued_and_running():
    sched = FIFOScheduler(slots=1)
    a = Request(uid=0, prompt=[1], max_new_tokens=50, deadline=3)
    b = Request(uid=1, prompt=[1], max_new_tokens=5, deadline=4)
    sched.submit(a, 0)
    sched.submit(b, 0)
    sched.admit(0)                       # a runs, b waits
    assert sched.expire(2) == ([], [])   # not yet
    eq, er = sched.expire(3)             # a overdue while running
    assert eq == [] and er[0][1] is a and a.status == "expired"
    sched.admit(3)                       # b takes the freed slot
    eq, er = sched.expire(4)             # b overdue while running
    assert er[0][1] is b and b.reason == "deadline"
    assert not sched.has_work()


# ---------------------------------------------------------------------------
# The same properties through the real engine (tiny model)
# ---------------------------------------------------------------------------

def _tiny_engine(slots, max_queue=None):
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config, reduced
    from repro.models.common import split_tree
    from repro.models.lm import init_lm
    from repro.serving.engine import ServingEngine
    cfg = reduced(get_config("qwen3-0.6b"))
    params = split_tree(init_lm(jax.random.PRNGKey(0), cfg))[0]
    return ServingEngine(params, cfg, slots=slots, max_seq=32,
                         max_queue=max_queue, cache_dtype=jnp.float32)


@given(seed=st.integers(0, 1_000))
@settings(max_examples=5, deadline=None)
def test_engine_random_workload_full_accounting(seed):
    rng = random.Random(seed)
    engine = _tiny_engine(slots=2, max_queue=3)
    reqs = [Request(uid=i,
                    prompt=[rng.randint(1, 90) for _ in
                            range(rng.randint(1, 5))],
                    max_new_tokens=rng.randint(1, 6),
                    deadline=rng.choice([None, None, rng.randint(2, 25)]))
            for i in range(7)]
    for r in reqs[:4]:
        engine.submit(r)
    for _ in range(3):                   # mid-flight arrivals
        engine.step()
    for r in reqs[4:]:
        engine.submit(r)
    engine.run_to_completion(max_steps=400)
    assert engine.step_count < 400, "engine failed to drain"
    terminal = {r.uid for r in engine.finished} \
        | {r.uid for r in engine.expired} \
        | {r.uid for r in engine.rejected}
    assert terminal == {r.uid for r in reqs}
    for r in engine.finished:
        assert len(r.output) == r.max_new_tokens
        assert r.latency_steps is not None and r.latency_steps > 0
    for r in reqs:
        assert r.status in TERMINAL


class _LaunchFault(RuntimeError):
    """Stands in for anything the fused launch can throw (OOM, a kernel
    assert, an interconnect hiccup)."""


@given(seed=st.integers(0, 1_000))
@settings(max_examples=5, deadline=None)
def test_engine_step_failures_keep_full_accounting(seed):
    """Full accounting and slot exclusivity survive injected failures:
    the fused launch raises on randomly chosen invocations (the engine's
    step is failure-atomic, so the caller retries the identical step) and
    chaos ``serving.slot`` faults NaN random slots (quarantine). Still:
    ``done + rejected + expired + evicted + faulted == submitted``, no
    slot is leaked or double-booked, and the system drains."""
    from repro.chaos.inject import chaos
    from repro.chaos.schedule import FaultSchedule, FaultSpec

    rng = random.Random(seed)
    engine = _tiny_engine(slots=2, max_queue=3)
    crash_calls = {rng.randint(2, 15) for _ in range(rng.randint(1, 3))}
    real_step, calls = engine._step, {"n": 0}

    def flaky(*args):
        calls["n"] += 1
        if calls["n"] in crash_calls:
            raise _LaunchFault(f"injected launch failure #{calls['n']}")
        return real_step(*args)

    engine._step = flaky
    schedule = FaultSchedule(seed=seed, faults=tuple(
        FaultSpec("chaos.serving.slot", rng.randint(2, 12), "nan",
                  value=float(rng.randrange(2)))
        for _ in range(rng.randint(1, 2))))
    reqs = [Request(uid=i,
                    prompt=[rng.randint(1, 90) for _ in
                            range(rng.randint(1, 5))],
                    max_new_tokens=rng.randint(1, 6),
                    deadline=rng.choice([None, None, rng.randint(2, 25)]))
            for i in range(8)]
    with chaos(schedule):
        for r in reqs[:5]:
            engine.submit(r)
        evict_uid = rng.choice([None, reqs[0].uid])
        ok_steps = failures = 0
        while engine.sched.has_work() and ok_steps < 300:
            try:
                engine.step()
            except _LaunchFault:
                failures += 1
                continue          # failure-atomic: retry the identical step
            ok_steps += 1
            if ok_steps == 2:     # mid-flight arrivals + an eviction
                for r in reqs[5:]:
                    engine.submit(r)
                if evict_uid is not None:
                    engine.evict(evict_uid)
            live = [r.uid for r in engine.sched.slot_map if r is not None]
            assert len(live) == len(set(live)), "slot double-booked"

    assert ok_steps < 300, "engine failed to drain under injected failures"
    assert failures == len([c for c in crash_calls if c <= calls["n"]])
    terminal = (engine.finished + engine.rejected + engine.expired +
                engine.evicted + engine.faulted)
    assert len(terminal) == len(reqs), "a request was dropped or counted " \
        "twice under injected failures"
    assert {r.uid for r in terminal} == {r.uid for r in reqs}
    for r in engine.faulted:
        assert r.status == "faulted" and r.reason == "numeric_fault"
        assert r.finish_step >= 0
    # only successful launches advance the engine clock
    assert engine.step_count == ok_steps
    assert engine.sched.free_slots() == list(range(engine.slots))


def test_engine_evict_queued_request():
    engine = _tiny_engine(slots=1)
    a = Request(uid=0, prompt=[1, 2], max_new_tokens=3)
    b = Request(uid=1, prompt=[3, 4], max_new_tokens=3)
    engine.submit(a)
    engine.submit(b)
    engine.step()                        # a running, b queued
    assert engine.evict(1) is b and b.status == "evicted"
    assert engine.evict(99) is None      # unknown uid is a no-op
    engine.run_to_completion()
    assert [r.uid for r in engine.finished] == [0]
