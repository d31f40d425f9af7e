"""The program names its parts inside the compiled train step: every
dispatch site of the execution plan (``core.policy.dispatch_site``), the
coarse scopes ``tokenizer``, ``blocks``, ``head`` and ``optimizer``. The
names live in each op's metadata (``op_name``), which a profiler trace
carries as the op's name stack; they add no op to the program."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.spikingformer import get_spikingformer_config
from repro.core.policy import named_policy
from repro.core.spikingformer import init_spikingformer
from repro.train.loop import make_train_step
from repro.train.optimizer import OptimizerConfig, init_opt_state

COARSE = ("tokenizer", "blocks", "head", "optimizer")
BATCH = 4


def _names(op_name: str) -> set[str]:
    """Every name a stack holds, transformations unwrapped:
    ``jit(f)/transpose(jvp(blocks))/pssa.qkv/dot_general`` ->
    {jit(f), blocks, pssa.qkv, dot_general}."""
    out = set()
    for part in op_name.split("/"):
        for piece in part.split(";"):
            while (m := re.match(r"^(jvp|transpose|vmap)\((.*)\)$", piece)):
                piece = m.group(2)
            out.add(piece)
    return out


@pytest.fixture(scope="module", params=["jnp", "pallas-full"])
def compiled(request):
    cfg = get_spikingformer_config("spikingformer-smoke",
                                   policy=named_policy(request.param))
    params, state = init_spikingformer(jax.random.PRNGKey(0), cfg)
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=4)
    images = jnp.zeros((BATCH, cfg.image_size, cfg.image_size, 3))
    labels = jnp.arange(BATCH) % cfg.num_classes
    step = jax.jit(make_train_step(cfg, opt_cfg))
    text = step.lower(params, state, init_opt_state(params), images,
                      labels).compile().as_text()
    stacks = [s for s in re.findall(r'op_name="([^"]*)"', text)
              if s.startswith("jit(train_step)")]
    return cfg, stacks


def test_every_planned_site_names_its_ops(compiled):
    cfg, stacks = compiled
    seen = set().union(*map(_names, stacks))
    for row in cfg.execution_plan(BATCH):
        if row.note and "never dispatched" in row.note:
            continue                  # folded into a fused conv stage
        assert row.site in seen, row.site


def test_sites_fall_under_their_coarse_scope(compiled):
    """(A constant a scan hoists out of its body keeps the site's name but
    not the scan's scope: ``jit(train_step)/smlp.a/broadcast_in_dim``.)"""
    _, stacks = compiled
    for stack in stacks:
        names = _names(stack)
        if stack.endswith("/broadcast_in_dim"):
            continue
        if any(n.startswith("tokenizer.") for n in names):
            assert "tokenizer" in names, stack
        if names & {"pssa.qkv", "pssa.lif", "pssa.proj", "attn_qk",
                    "attn_av", "smlp.lif", "smlp.a", "smlp.b"}:
            assert "blocks" in names, stack


def test_the_coarse_scopes_cover_the_step(compiled):
    """Forward and backward of each coarse scope is in the program, and
    at most a handful of ops (calls, hoisted constants) sit outside all
    four."""
    _, stacks = compiled
    for scope in COARSE[:3]:
        assert any(f"jvp({scope})" in s and "transpose" not in s
                   for s in stacks), scope
        assert any(f"transpose(jvp({scope}))" in s for s in stacks), scope
    assert any(s.startswith("jit(train_step)/optimizer/") for s in stacks)
    outside = [s for s in stacks if not set(COARSE) & _names(s)]
    assert len(outside) <= 0.01 * len(stacks), outside[:10]


def test_the_optimizer_scope_holds_the_update_and_the_guard(compiled):
    _, stacks = compiled
    ops = {s.rsplit("/", 1)[-1] for s in stacks
           if s.startswith("jit(train_step)/optimizer/")}
    assert {"is_finite", "select_n", "sqrt"} <= ops
