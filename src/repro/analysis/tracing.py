"""Trace-count guards: fail loudly when jit recompiles more than planned.

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
* with no callables, a process-global compile counter hooked off jax's
  compilation log records, covering jits created *inside* the block.
"""
from __future__ import annotations

import contextlib
import logging
from typing import Any, Callable, Iterator

__all__ = ["assert_trace_count", "compile_counter", "trace_count"]

#: Logger jax emits per-compilation records on, at DEBUG.
_DISPATCH_LOGGER = "jax._src.dispatch"
_COMPILE_MARKER = "Finished XLA compilation"


def trace_count(fn: Callable[..., Any]) -> int:
    """Number of traces a jitted callable has compiled so far."""
    return fn._cache_size()


class _CompileCountHandler(logging.Handler):
    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.count = 0

    def emit(self, record: logging.LogRecord) -> None:
        if _COMPILE_MARKER in record.getMessage():
            self.count += 1


@contextlib.contextmanager
def compile_counter() -> Iterator[Callable[[], int]]:
    """Context manager yielding a zero-argument callable that returns the
    number of XLA compilations since the block was entered (process-global,
    any jit)."""
    log = logging.getLogger(_DISPATCH_LOGGER)
    handler = _CompileCountHandler()
    prev_level = log.level
    log.addHandler(handler)
    # jax logs compiles at DEBUG unless jax_log_compiles promotes them;
    # lower only this logger (records still propagate to root, whose
    # WARNING-level handlers ignore them — no console noise).
    if log.getEffectiveLevel() > logging.DEBUG:
        log.setLevel(logging.DEBUG)
    try:
        yield lambda: handler.count
    finally:
        log.removeHandler(handler)
        log.setLevel(prev_level)


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
