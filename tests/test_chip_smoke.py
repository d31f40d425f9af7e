"""chip_smoke.py off the chip: its phases at the CPU smoke size, and its
refusal to run anything where JAX finds no TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import chip_diag
import chip_smoke

ROOT = Path(chip_smoke.__file__).resolve().parent
PRESET = "spikingformer-smoke"
BATCH = 4


def test_kernel_phase_matches_oracles():
    assert chip_smoke.check_kernels(chip_smoke.arm_config("jnp", PRESET),
                                    BATCH) == []


def test_train_phase_runs_every_arm_cleanly():
    runs = {}
    with chip_smoke.PolicyRecords() as records:
        for arm in chip_smoke.ARMS:
            cfg = chip_smoke.arm_config(arm, PRESET)
            runs[arm] = chip_smoke.train_arm(cfg, BATCH, steps=2)
            assert chip_smoke.arm_failures(cfg, BATCH, runs[arm]) == []
    plans = [chip_smoke.arm_config(a, PRESET).execution_plan(BATCH)
             for a in chip_smoke.ARMS]
    assert records.failures(plans) == []
    loss0 = {arm: run["losses"][0] for arm, run in runs.items()}
    assert all(abs(v - loss0["jnp"]) < 1e-4 for v in loss0.values())


@pytest.fixture(scope="module")
def mesh():
    from repro.launch.mesh import make_test_mesh

    return make_test_mesh(1, 1, devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def jnp_passes(mesh):
    return chip_smoke.composed_run(chip_smoke.arm_config("jnp", PRESET),
                                   BATCH, mesh)


@pytest.mark.parametrize("arm", ["pallas", "pallas-full"])
def test_composed_phase_agrees_with_jnp(arm, mesh, jnp_passes):
    got = chip_smoke.composed_run(chip_smoke.arm_config(arm, PRESET), BATCH,
                                  mesh)
    assert set(got) == set(jnp_passes)
    assert chip_smoke._gap_failures(
        arm, chip_smoke.composed_gaps(got, jnp_passes)) == []


@pytest.mark.parametrize("plant", sorted(chip_diag.PLANTS))
def test_composed_phase_fails_on_a_planted_bn_fault(plant, mesh, jnp_passes):
    with chip_diag.PLANTS[plant]():
        got = chip_smoke.composed_run(chip_smoke.arm_config("pallas", PRESET),
                                      BATCH, mesh)
    assert chip_smoke._gap_failures(
        plant, chip_smoke.composed_gaps(got, jnp_passes)) != []


def test_four_chip_check_passes_sharded_and_fails_dropped_reductions():
    """On four virtual CPU devices: the sharded passes agree with one
    device's, and one shard's gradients alone lie outside the bound (the
    function fails unless both hold)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = ("import chip_smoke; chip_smoke._import_repro(); import jax; "
            "print(chip_smoke.sharding_failures("
            f"chip_smoke.arm_config('jnp', {PRESET!r}), 8, jax.devices()))")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert "sharded vs single" in proc.stdout and "dropped" in proc.stdout
    assert lines[-1] == "[]"


def test_arm_failures_flag_nan_and_breaker_trips():
    cfg = chip_smoke.arm_config("jnp", PRESET)
    run = {"losses": [2.3, float("nan")], "trips": {"pssa.qkv": object()}}
    fails = chip_smoke.arm_failures(cfg, BATCH, run)
    assert any("non-finite" in f for f in fails)
    assert any("circuit breaker" in f for f in fails)


def test_policy_records_fail_on_warnings_and_unplanned_decisions():
    from repro.core.policy import logger, runtime_fallback

    with chip_smoke.PolicyRecords() as records:
        logger.warning("execution policy: something degraded")
        runtime_fallback("smoke.unplanned", "pallas", "a reason nobody "
                         "planned", expected=True)
    fails = records.failures([[]])
    assert any("WARNING" in f for f in fails)
    assert any("no plan reports" in f for f in fails)


def test_off_the_chip_it_exits_nonzero_before_any_phase(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert proc.stdout == ""          # no phase, no verdict line
