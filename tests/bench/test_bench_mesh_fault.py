"""The exchange between chips left out, on a (data=4) mesh of four virtual
CPU devices in a process of its own: the fault a four-chip cell can have
(the mix `bench/traffic/jnp.in224.b16.data4.json` is ready for one)."""
import json
import os
import subprocess
import sys

from bench.cells import ROOT

SCRIPT = """
import json, math, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import faults
from bench.cells import BENCH, Cell, family, load_json
from bench.compare import NUMBERS
from tests.bench._tiny import run, tiny
model = load_json(BENCH / "configs/spikingformer-8-512.json")
cell = tiny(Cell("mesh", 4, model,
                 load_json(BENCH / "traffic/jnp.in224.b16.data4.json"),
                 {{k: math.inf for k in NUMBERS}}, (), (),
                 family(model["family"])), batch=16)
sound = run(cell, jax.devices()[:4], seed=3)
broken = run(cell, jax.devices()[:4], seed=3,
             hook=faults.no_exchange(cell))
print(json.dumps([{{k: v["value"] for k, v in out["compared"].items()}}
                  for out in (sound, broken)]))
"""


def test_the_exchange_left_out_moves_the_statistics():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    sound, broken = json.loads(proc.stdout.splitlines()[-1])
    # each chip's BN statistics over its own 4 images, not all 16
    assert broken["stats_gap"] > 0.01
    assert broken["stats_gap"] > 100 * sound["stats_gap"]
