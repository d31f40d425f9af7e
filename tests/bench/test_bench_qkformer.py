"""The QKFormer cell, cut to the smoke size here (three stages at widths 16 /
32 / 64, one block each, T=2, 32x32 images, 10 classes), run through the
harness on the CPU: a sound run is ``correct``, the bfloat16 control and a
state left unchanged are not. Also the FLOP count of the published
configuration, written out layer by layer, and the scopes the program's
step carries."""
import copy
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from bench import cells, faults
from tests.bench._tiny import run

CELL = "qkf10-768.jnp.in224.b4"
SMOKE = dict(widths=[16, 32, 64], depths=[1, 1, 1], time_steps=2,
             image_size=32, num_classes=10)


@pytest.fixture(scope="module")
def cell():
    cell = cells.find_cell(CELL)
    model = copy.deepcopy(cell.model)
    model["model"].update(SMOKE)
    return dataclasses.replace(cell, model=model)


def test_a_sound_run_is_correct(cell):
    out = run(cell, jax.devices()[:1], seed=2**31 + 21)
    assert out["correct"], out["compared"]
    assert set(out["compared"]) == set(cell.limits)


@pytest.mark.parametrize("broken", ["control", "unchanged"])
def test_a_broken_step_is_not_correct(cell, broken):
    hook = faults.control(cell) if broken == "control" else faults.unchanged
    out = run(cell, jax.devices()[:1], seed=2**31 + 21, hook=hook)
    assert not out["correct"], out["compared"]


def test_step_flops_of_the_published_configuration():
    """Batch 4, T=4: 16 image-steps. Multiply-adds of each layer."""
    tb = 16
    embed = [
        tb * 224 * 224 * 9 * 3 * 96,        # stem conv 3 -> 96 at 224
        tb * 112 * 112 * 9 * 96 * 192,      # 96 -> 192 at 112
        tb * 56 * 56 * 9 * 192 * 192,       # 192 -> 192 at 56
        tb * 56 * 56 * 96 * 192,            # shortcut 1x1 stride 2
        tb * 56 * 56 * 9 * 192 * 384,       # stage 2: 192 -> 384 at 56
        tb * 56 * 56 * 9 * 384 * 384,       # 384 -> 384 at 56, then pooled
        tb * 28 * 28 * 192 * 384,           # shortcut
        tb * 28 * 28 * 9 * 384 * 768,       # stage 3: 384 -> 768 at 28
        tb * 28 * 28 * 9 * 768 * 768,       # 768 -> 768 at 28, then pooled
        tb * 14 * 14 * 384 * 768,           # shortcut
    ]
    qkta_1 = 3 * tb * 3136 * 192 * 192      # Q, K, proj at 56x56 tokens
    mlp_1 = 2 * tb * 3136 * 192 * 768
    qkta_2 = 3 * tb * 784 * 384 * 384
    mlp_2 = 2 * tb * 784 * 384 * 1536
    ssa_3 = 4 * tb * 196 * 768 * 768 + 2 * tb * 196 * 196 * 768
    mlp_3 = 2 * tb * 196 * 768 * 3072
    head = 4 * 768 * 1000
    macs = (sum(embed) + (qkta_1 + mlp_1) + 2 * (qkta_2 + mlp_2)
            + 7 * (ssa_3 + mlp_3) + head)
    published = cells.find_cell(CELL)
    assert published.family.step_flops(published.model, 4) == 6.0 * macs


def test_the_step_names_its_stages_and_sites(cell):
    """The compiled smoke step's name stacks hold the coarse scopes and
    the QKTA sites, forward and backward."""
    from repro.launch.mesh import make_test_mesh, use_mesh

    mesh = make_test_mesh(1, 1, devices=jax.devices()[:1])
    with use_mesh(mesh):
        (params, state, opt), fn, plan = cell.family.program(cell, mesh)
        text = jax.jit(fn).lower(params, state, opt, jnp.zeros(
            (4, 32, 32, 3)), jnp.arange(4)).compile().as_text()
    stacks = re.findall(r'op_name="([^"]*)"', text)
    names = set()
    for stack in stacks:
        names.update(re.split(r"[/();]", stack))
    for scope in ("embed", "qkta_blocks", "ssa_blocks", "head", "optimizer",
                  "qkta.qk", "qkta.mask", "qkta.proj", "qkta.lif",
                  "speds.conv.0", "speds.shortcut.2", "ssa.qkv", "mlp.a"):
        assert scope in names, scope
    assert any("transpose" in s and "qkta.mask" in s for s in stacks)
    assert plan["qkta.mask"] == "jnp"

