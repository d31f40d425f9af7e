"""Trace-count guards and host spans.

Trace-count guards fail loudly when jit recompiles more than planned.
The serving engine's single-trace contract (ONE jit trace for the engine's
lifetime, ``docs/SERVING.md``) was asserted ad hoc via the jitted step's
``_cache_size()``. This module generalizes that into a reusable guard so
*any* hot path — ``make_train_step``, the serving step, a benchmark loop —
can pin its compile count in tests and retrace regressions (a policy that
stops hashing stably, a shape that silently varies per step) fail with an
assertion instead of a 100x slowdown:

    step = jax.jit(train_step)
    with assert_trace_count(1, step):
        for batch in batches:
            step(state, batch)

Two mechanisms, used automatically:

* with explicit jitted callables, the per-function compile-cache size
  (``fn._cache_size()``) before/after the block;
* with no callables, a process-global compile counter listening on jax's
  documented ``jax.monitoring`` compile event, covering jits created
  *inside* the block.

Host spans (:func:`span`) name a stretch of host work, such as placing a
batch or waiting for a step's loss. Each span is a
``jax.profiler.TraceAnnotation``, so in a profiler trace it lies on the
same clock as the device's ops; it is also always added to an in-process
registry (count, total and longest seconds per name) that
:func:`span_stats` reads, whether a profiler runs or not:

    with span("data.place"):
        batch = place_batch(host_batch, mesh)
    span_stats()["data.place"]   # {"count": 1, "total_s": ..., "max_s": ...}
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Iterator

import jax

__all__ = ["assert_trace_count", "compile_counter", "reset_spans", "span",
           "span_stats", "trace_count"]

#: The ``jax.monitoring`` duration event jax records once per executable
#: it builds (compiled, or read from the persistent compilation cache).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def trace_count(fn: Callable[..., Any]) -> int:
    """Number of traces a jitted callable has compiled so far."""
    return fn._cache_size()


@contextlib.contextmanager
def compile_counter() -> Iterator[Callable[[], int]]:
    """Context manager yielding a zero-argument callable that returns the
    number of XLA compilations since the block was entered (process-global,
    any jit)."""
    count = 0

    def listen(event: str, duration: float, **_: Any) -> None:
        nonlocal count
        if event == COMPILE_EVENT:
            count += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield lambda: count
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@contextlib.contextmanager
def assert_trace_count(n: int, *fns: Callable[..., Any],
                       exact: bool = True) -> Iterator[None]:
    """Assert the block compiles exactly (``exact=True``, default) or at
    most (``exact=False``) ``n`` traces.

    With jitted callables given, each one's compile-cache delta is checked
    independently against ``n``; with none, the process-global compile
    count for the block is checked (covering jits created inside it).
    """
    if fns:
        before = [trace_count(f) for f in fns]
        yield
        for f, b in zip(fns, before):
            _check(trace_count(f) - b, n, exact, getattr(f, "__name__", repr(f)))
    else:
        with compile_counter() as count:
            yield
            _check(count(), n, exact, "block")


def _check(got: int, want: int, exact: bool, what: str) -> None:
    if got != want if exact else got > want:
        bound = "exactly" if exact else "at most"
        raise AssertionError(
            f"trace-count guard: {what} compiled {got} trace(s), "
            f"expected {bound} {want} — a retrace regression (unstable "
            f"static arg hash, or shapes varying per call?)")


_SPANS: dict[str, list[float]] = {}      # name -> [count, total_s, max_s]
_SPANS_LOCK = threading.Lock()


class span:
    """``with span(name):`` -- a host span: a profiler annotation named
    ``name``, and one more entry of ``name`` in the registry that
    :func:`span_stats` reads. Spans nest; each one counts its own time.
    Costs two clock reads and a dict update when no profiler runs."""

    __slots__ = ("name", "_annotation", "_t0")

    def __init__(self, name: str) -> None:
        self.name = name
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        with _SPANS_LOCK:
            rec = _SPANS.setdefault(self.name, [0, 0.0, 0.0])
            rec[0] += 1
            rec[1] += dt
            rec[2] = max(rec[2], dt)


def span_stats() -> dict[str, dict[str, float]]:
    """Snapshot of the span registry: name -> ``count``, ``total_s`` and
    ``max_s`` of every span of that name since the process started (or
    since :func:`reset_spans`)."""
    with _SPANS_LOCK:
        return {name: {"count": int(c), "total_s": t, "max_s": m}
                for name, (c, t, m) in _SPANS.items()}


def reset_spans() -> None:
    """Empty the span registry."""
    with _SPANS_LOCK:
        _SPANS.clear()
