"""Shared fixtures and defaults for the test suite.

* Puts ``src/`` and the checkout root on ``sys.path`` so ``pytest -q``
  works without exporting ``PYTHONPATH`` (the tier-1 command still sets
  it; both are fine) and tests can import ``chip_smoke``.
* Pins CPU-safe numeric defaults: x64 stays off so tolerances mean the same
  thing everywhere the suite runs.
* ``rng_key`` / ``make_key`` fixtures replace hand-rolled ``PRNGKey`` calls —
  fixed seeds, derived deterministically.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
for _p in (_ROOT, _ROOT / "src"):   # chip_smoke.py lives at the root
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

# CPU/x64-safe defaults: keep f32 semantics identical across machines and
# make sure a leaked XLA device-count flag never reaches this process.
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402  (after sys.path setup)
import pytest  # noqa: E402

# The suite is XLA-compile dominated; the persistent compilation cache
# (``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``, the same
# place every entry point uses) cuts warm reruns to a fraction of the cold
# time. Cache keys include the jax version and compile options, so it never
# masks behavior changes; every compile is cached, however short.
from repro.launch.cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@pytest.fixture
def rng_key():
    """The suite's fixed seed key. Split it; don't invent new seeds."""
    return jax.random.PRNGKey(0)


@pytest.fixture
def make_key():
    """Factory for auxiliary fixed-seed keys: ``make_key(i)``."""
    return jax.random.PRNGKey
