"""Continuous-batching serving engine with a persistent neuron-state cache.

The previous engine was wave-synchronous: requests were batched into waves,
every slot stepped until the *longest* request in the wave drained (finished
slots burned decode steps), and the whole cache was rebuilt from scratch per
wave. This engine replaces that with continuous batching:

* **Persistent slot-indexed state cache.** One device-resident cache of
  ``slots`` entries holds every slot's recurrent decode state — attention /
  MLA KV, SSM / RWKV recurrences, and (for spiking LMs, ``cfg.lif``) the
  per-layer LIF ``(U, S)`` membrane carry, the KV-cache analogue for
  neurons. It is created once and survives across steps; nothing is ever
  rebuilt.
* **Per-step admit/evict.** Each step, finished/evicted slots are freed and
  queued requests are admitted into them. An admitted slot's state is reset
  to init *inside the same fused step* (a masked zero-fill along the slot
  axis — see ``models.lm.reset_cache_slots``), so neighbours are never
  disturbed: prefill-into-slot happens while other slots keep generating.
* **Single-trace decode.** One jit'd fused step (slot reset + batched
  one-token decode) serves prefill (teacher-forcing prompt tokens) and
  generation for all slots; its shapes never change, so there is exactly
  ONE trace for the engine's lifetime (asserted by the test suite via the
  ``repro.analysis.tracing`` trace-count guard).
* **Scheduler.** A FIFO queue + slot map (``serving.scheduler``) with
  per-request deadlines, max-token budgets, and explicit (never silent)
  over-capacity rejection.
* **Slot quarantine.** Non-finite logits in a slot (docs/RESILIENCE.md)
  finish that request with the explicit ``faulted``/``numeric_fault``
  status, evict it, and flush the slot state to init — one bad slot never
  poisons its neighbours or the next occupant, and the single-trace
  contract is preserved (the flush reuses the eviction reset jit).

Greedy (temperature=0) decode of a slot matches serving the request alone —
slot isolation is proven token-for-token (up to float-tie tolerance: the
solo B=1 and slotted B=N executables may reassociate reductions) by
``tests/test_serving_continuous.py``, including admissions into slots
another request just vacated.
"""
from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.tracing import trace_count
from repro.chaos import inject as chaos_inject
from repro.configs.base import ArchConfig
from repro.models.lm import (cache_slot_state, init_cache, lm_decode_step,
                             reset_cache_slots)
from repro.serving.scheduler import FIFOScheduler, Request, SlotError

__all__ = ["Request", "ServingEngine", "SlotError"]


class ServingEngine:
    """Continuous-batching LM server over a fixed number of decode slots.

    Parameters mirror the model: ``params``/``cfg`` from ``init_lm``;
    ``slots`` is the decode batch width; ``max_seq`` bounds prompt + new
    tokens per request; ``max_queue`` caps the waiting queue (None =
    unbounded; over-capacity submits are rejected explicitly).
    """

    def __init__(self, params: Any, cfg: ArchConfig, *, slots: int = 8,
                 max_seq: int = 512, temperature: float = 0.0, seed: int = 0,
                 cache_dtype=jnp.float32, max_queue: int | None = None):
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_seq = max_seq
        self.temperature = temperature
        self._rng = np.random.default_rng(seed)

        self.sched = FIFOScheduler(slots, max_queue)
        self.finished: list[Request] = []
        self.rejected: list[Request] = []
        self.expired: list[Request] = []
        self.evicted: list[Request] = []
        #: Requests quarantined for non-finite logits (status "faulted",
        #: reason "numeric_fault") — the slot was evicted and its state
        #: flushed to init; the engine itself keeps serving.
        self.faulted: list[Request] = []

        # Device-resident persistent state: created once, never rebuilt.
        self.cache = init_cache(cfg, slots, max_seq, cache_dtype)

        # Host-side per-slot bookkeeping.
        self._pos = np.zeros(slots, np.int32)
        self._next_tok = np.zeros((slots, 1), np.int32)
        self._prefill_idx = [0] * slots
        self._pending_reset: set[int] = set()

        # Counters (the bench reads these).
        self.step_count = 0
        self.active_slot_steps = 0
        self.generated_tokens = 0
        self.decode_seconds = 0.0

        def fused_step(p, cache, tokens, pos, reset_mask):
            # Slot reset rides inside the decode launch: admitted slots are
            # zero-filled, then every slot advances one token. One trace.
            cache = reset_cache_slots(cache, reset_mask, cfg)
            return lm_decode_step(p, cache, tokens, pos, cfg)

        self._step = jax.jit(fused_step)
        self._reset = jax.jit(
            lambda cache, mask: reset_cache_slots(cache, mask, cfg))

    # -- submission / cancellation ------------------------------------------

    def submit(self, req: Request) -> bool:
        """Queue a request. Returns False — with ``req.status ==
        "rejected"`` and a reason, and the request recorded in
        ``self.rejected`` — when the prompt + token budget cannot fit in
        ``max_seq`` or the queue is at capacity. Never drops silently."""
        if not req.prompt or len(req.prompt) + req.max_new_tokens > \
                self.max_seq:
            req.status, req.reason = "rejected", "too_long"
            self.rejected.append(req)
            return False
        if not self.sched.submit(req, self.step_count):
            self.rejected.append(req)
            return False
        return True

    def evict(self, uid: int) -> Request | None:
        """Cancel a queued or running request. A running request's slot is
        freed and its state reset to init *immediately* (not lazily at the
        next admit), so nothing leaks into the next occupant even if the
        engine idles. Returns the request, or None if it is not live."""
        slot, req = self.sched.find(uid)
        if req is None:
            return None
        if slot is None:
            self.sched.queue.remove(req)
        else:
            self.sched.release(slot)
            self._clear_slot(slot)
            self.flush_resets()
        req.status, req.reason = "evicted", "evicted"
        req.finish_step = self.step_count
        self.evicted.append(req)
        return req

    # -- the engine step -----------------------------------------------------

    def step(self) -> None:
        """One engine step: deadline sweep -> admit queued requests into
        free slots -> ONE fused batched launch (masked slot reset + decode)
        -> per-slot teacher-force/sample bookkeeping -> free finished slots.
        """
        now = self.step_count
        expired_queued, expired_running = self.sched.expire(now)
        self.expired.extend(expired_queued)
        for slot, req in expired_running:
            self._clear_slot(slot)
            self.expired.append(req)

        reset_mask = np.zeros(self.slots, bool)
        for slot in self._pending_reset:
            reset_mask[slot] = True
        self._pending_reset.clear()
        for slot, req in self.sched.admit(now):
            reset_mask[slot] = True
            self._pos[slot] = 0
            self._next_tok[slot, 0] = req.prompt[0]
            self._prefill_idx[slot] = 1

        t0 = time.perf_counter()
        # .copy() the host arrays: on CPU, device_put can zero-copy ALIAS a
        # numpy buffer while dispatch is async, and the bookkeeping below
        # mutates _next_tok/_pos in place — handing jax the live arrays
        # races the in-flight launch (nondeterministic logits under load).
        try:
            logits, self.cache = self._step(self.params, self.cache,
                                            jnp.asarray(self._next_tok.copy()),
                                            jnp.asarray(self._pos.copy()),
                                            jnp.asarray(reset_mask))
        except BaseException:
            # Failure atomicity: the launch consumed nothing (self.cache is
            # unchanged) but the pending resets were already drained into
            # reset_mask — put them back so a retried step re-applies them.
            # Admitted slots keep their bookkeeping; the retry relaunches
            # the identical step (step_count was not incremented).
            self._pending_reset.update(
                s for s in range(self.slots) if reset_mask[s])
            raise
        self.step_count += 1
        lg = None   # fetched lazily: pure-prefill steps skip the transfer
        for slot, req in enumerate(self.sched.slot_map):
            if req is None:
                self._pos[slot] = 0
                self._next_tok[slot, 0] = 0
                continue
            self.active_slot_steps += 1
            self._pos[slot] += 1
            if self._prefill_idx[slot] < len(req.prompt):
                self._next_tok[slot, 0] = req.prompt[self._prefill_idx[slot]]
                self._prefill_idx[slot] += 1
                continue
            if lg is None:
                lg = chaos_inject.serving_fault(np.asarray(logits), now)
            row = lg[slot]
            if not np.all(np.isfinite(row)):
                self._quarantine(slot, req)
                continue
            tok = self._sample(row)
            if req.first_token_step < 0:
                req.first_token_step = self.step_count
            req.output.append(tok)
            self.generated_tokens += 1
            self._next_tok[slot, 0] = tok
            if len(req.output) >= req.max_new_tokens or \
                    int(self._pos[slot]) >= self.max_seq:
                self._finish(slot, req)
        self.decode_seconds += time.perf_counter() - t0

    def run_to_completion(self, max_steps: int = 100_000) -> list[Request]:
        """Step until queue and slots drain (or ``max_steps``); returns the
        completed requests."""
        while self.sched.has_work() and self.step_count < max_steps:
            self.step()
        return self.finished

    # -- inspection ----------------------------------------------------------

    @property
    def occupancy(self) -> float:
        """Fraction of slot-steps so far that served a live request (the
        wave engine's drained slots scored ~1/slots here on skewed loads)."""
        return self.active_slot_steps / max(1, self.step_count * self.slots)

    def flush_resets(self) -> None:
        """Apply pending slot resets now. Normal operation folds them into
        the next fused step; eviction (and state inspection) calls this
        eagerly so freed slots verifiably hold init state."""
        if not self._pending_reset:
            return
        mask = np.zeros(self.slots, bool)
        mask[list(self._pending_reset)] = True
        self.cache = self._reset(self.cache, jnp.asarray(mask))
        self._pending_reset.clear()

    def slot_state(self, slot: int):
        """One slot's decode-state slice (pending resets applied first)."""
        self.flush_resets()
        return cache_slot_state(self.cache, slot, self.cfg)

    def trace_count(self) -> int:
        """Number of traces the fused step has compiled (the single-trace
        contract says this is 1). Delegates to :func:`repro.analysis.tracing.trace_count`, the same
        guard the trace-count tests pin ``make_train_step`` with."""
        return trace_count(self._step)

    # -- internals -----------------------------------------------------------

    def _clear_slot(self, slot: int) -> None:
        self._pending_reset.add(slot)
        self._pos[slot] = 0
        self._next_tok[slot, 0] = 0
        self._prefill_idx[slot] = 0

    def _quarantine(self, slot: int, req: Request) -> None:
        """Non-finite logits in a slot (kernel bug, state corruption, an
        injected ``chaos.serving.slot`` fault): evict the request with the
        explicit ``numeric_fault`` status and flush the slot's state to
        init *eagerly* — the corruption must not leak into the next
        occupant. No retrace: the flush rides the same ``_reset`` jit
        eviction uses, and the fused step's trace never changes."""
        req.status, req.reason = "faulted", "numeric_fault"
        req.finish_step = self.step_count
        self.sched.release(slot)
        self._clear_slot(slot)
        self.flush_resets()
        self.faulted.append(req)

    def _finish(self, slot: int, req: Request) -> None:
        req.done = True
        req.status = "done"
        req.finish_step = self.step_count
        self.sched.release(slot)
        self._clear_slot(slot)
        self.finished.append(req)

    def _sample(self, logits_row: np.ndarray) -> int:
        if self.temperature == 0.0:
            return int(np.argmax(logits_row))
        z = logits_row / self.temperature
        e = np.exp(z - z.max())
        return int(self._rng.choice(len(z), p=e / e.sum()))
