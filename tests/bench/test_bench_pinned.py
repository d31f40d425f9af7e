"""Readings that a change to the harness must leave as they are, pinned
to the value (``data/pinned.json``): the first steps' readings of the
program and of the reference in tiny CPU runs of two cells, the numbers
compared, and the operation and byte counts of both configurations.

The runs are made in a process of their own, on one CPU core, with one
CPU device and no persistent compilation cache: XLA's CPU backend splits
work by the cores and devices it is given, which moves float32 results by
about 1e-7, and a cached executable may have been compiled for others."""
import collections
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import cells
from bench.cells import ROOT
from tests.bench import _tiny
from tests.bench.test_bench_work import PALLAS_FULL_B8

DATA = Path(__file__).resolve().parent / "data" / "pinned.json"
PINNED = json.loads(DATA.read_text())
RUNS = {"sf8-512.jnp.in224.b8": 8, "sf2-256-dvs.jnp.t16.b16": 16}
SEED = 2**31 + 77
#: A plan with megakernel sites on their fused arm.
FUSED = dict(PALLAS_FULL_B8, **{"pssa.qkv": "fused_epilogue",
                                "smlp.a": "fused_epilogue",
                                "tokenizer.conv.1": "fused_epilogue"})
PLANS = {"pallas-full": PALLAS_FULL_B8, "fused": FUSED}

SCRIPT = """
import json, os, sys
os.sched_setaffinity(0, {{min(os.sched_getaffinity(0))}})
sys.path[:0] = [{root!r}, {src!r}]
from tests.bench.test_bench_pinned import readings
print(json.dumps(readings()))
"""


def _plain(x):
    if isinstance(x, dict):
        return {k: _plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_plain(v) for v in x]
    if isinstance(x, np.ndarray):
        return x.tolist()
    return float(x)


def readings() -> dict:
    """Per cell, the readings ``compare.gaps`` is handed in a tiny run
    (``got``, ``want``) and the numbers it returns."""
    import jax

    from bench import compare

    out, gaps = {}, compare.gaps
    for name, batch in RUNS.items():
        def keep(got, want, name=name):
            out[name] = {"got": got, "want": want,
                         "numbers": gaps(got, want)}
            return out[name]["numbers"]

        compare.gaps = keep
        try:
            _tiny.run(_tiny.tiny_cell(name, batch), jax.devices()[:1],
                      seed=SEED, seconds=0.1)
        finally:
            compare.gaps = gaps
    return _plain(out)


def measured_readings() -> dict:
    """:func:`readings` in a process of its own, on one core."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    code = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def counts(config: str) -> dict:
    """Model FLOPs and each kernel family's pieces for one configuration,
    at the batch of its cell and around it."""
    name = next(w for w in RUNS
                if cells.find_cell(w).model["name"] == config)
    cell, batch = cells.find_cell(name), RUNS[name]
    out = {"step_flops": [_tiny.step_flops(cell, b)
                          for b in (1, batch, 2 * batch)]}
    for kind in ("lif", "spike_mm"):
        for plan_name, plan in PLANS.items():
            pieces = collections.Counter(
                _tiny.kernel_work(kind, cell, batch, plan))
            out[f"{kind}.{plan_name}"] = sorted(
                [float(f), float(b), n] for (f, b), n in pieces.items())
    return out


@pytest.fixture(scope="module")
def measured():
    return measured_readings()


@pytest.mark.parametrize("name", sorted(RUNS))
def test_the_first_steps_read_as_pinned(measured, name):
    got, want = measured[name], PINNED["readings"][name]
    assert got["numbers"] == pytest.approx(want["numbers"], rel=1e-12)
    for side in ("got", "want"):
        assert set(got[side]) == set(want[side])
        for key, value in want[side].items():
            if key == "stats":
                assert len(got[side][key]) == len(value)
                for a, b in zip(got[side][key], value):
                    np.testing.assert_allclose(a, b, rtol=1e-12, atol=0)
            else:
                np.testing.assert_allclose(got[side][key], value,
                                           rtol=1e-12, atol=0)


@pytest.mark.parametrize("config", ["spikingformer-8-512",
                                    "spikingformer-2-256-dvs"])
def test_the_work_counts_read_as_pinned(config):
    assert counts(config) == PINNED["counts"][config]
