"""A whole run on the CPU at a tiny size, past the look for a chip: the
result line, and ``correct`` against the timed path broken underneath."""
import jax
import pytest

from bench import faults
from tests.bench._tiny import run, tiny_cell

CELL = "sf8-512.jnp.in224.b8"
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def cell():
    return tiny_cell(CELL)


@pytest.fixture(scope="module")
def sound(cell):
    return run(cell, jax.devices()[:1], seed=2**31 + 11)


def test_the_result_line_has_the_contract_keys(sound, cell):
    assert list(sound) == KEYS + ["compared"]
    assert set(sound["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(set(v) == {"value", "unit"} for v in sound["metrics"].values())
    assert set(sound["device"]) == {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["compared"]) == set(cell.limits)
    assert all(set(v) == {"value", "limit"}
               for v in sound["compared"].values())


def test_a_traced_run_reports_per_layer_metrics(cell):
    out = run(cell, jax.devices()[:1], trace=True)
    assert list(out) == KEYS + ["breakdown", "compared"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert out["device"]["window_s"] > 0
    assert set(out["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert "host.batch_ms" in out["metrics"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_sound_run_is_correct(sound):
    assert sound["correct"], sound["compared"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(cell, fault):
    out = run(cell, jax.devices()[:1], hook=getattr(faults, fault))
    assert not out["correct"], out["compared"]


def test_the_bfloat16_control_is_not_correct(cell):
    out = run(cell, jax.devices()[:1], hook=faults.control(cell))
    assert not out["correct"], out["compared"]
