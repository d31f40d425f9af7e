"""A model family is new files alone: a toy family that is no
Spikingformer (token ids into an embedding and an MLP, no BatchNorm)
written with its configuration, reference, traffic mix and limits into a
root of its own, and driven through the harness on the CPU."""
import math
import re
import textwrap
import time

import jax
import pytest

from bench import cells, faults, harness
from bench.cells import BENCH, load_json

CELL = "toy-mlp.tokens.b16"
PEAKS = load_json(BENCH / "peaks.json")["TPU v5 lite"]

BENCHMARK = """{
  "command": ["python3", "bench/run.py"], "paths": ["bench"],
  "run_seconds": 1,
  "configs": [{"name": "toy-mlp", "source": "a test", "file":
               "bench/configs/toy-mlp.json", "reduced": [], "why": "a test"}],
  "workloads": [{"name": "toy-mlp.tokens.b16", "config": "toy-mlp",
                 "traffic": "tokens.b16", "chips": 1, "why": "a test"}],
  "end_to_end": [
    {"name": "sequences_per_s", "unit": "seq/s", "better": "higher",
     "bound": 0.1, "source": "host_clock"},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
     "source": "host_clock"}],
  "per_layer": [
    {"name": "step_mfu", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "train step",
     "moves": "sequences_per_s"},
    {"name": "lif_roofline", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "kernels",
     "moves": "sequences_per_s"}]
}"""

CONFIG = """{"name": "toy-mlp", "family": "toy",
 "model": {"vocab": 97, "seq": 12, "d_model": 32, "d_ff": 64, "classes": 5},
 "optimizer": {"lr": 0.01, "beta1": 0.9, "beta2": 0.95, "eps": 1e-08}}"""

MIX = """{"batch": 16, "mesh": {"data": 1, "model": 1}}"""

#: Set from the toy's readings on the CPU over three seeds: the program
#: reads 0 to 7e-8 on every number; the bfloat16 control 3.7e-4 and more
#: on grad_gap, 8.9e-4 on loss_gap, 7.9e-3 on change_gap.
LIMITS = """{"loss_gap": 1e-05, "grad_gap": 1e-05, "change_gap": 1e-05}"""

FAMILY = '''
"""The toy family: mean of token embeddings, a ReLU MLP, cross-entropy,
Adam; no non-parameter state."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

THROUGHPUT = "sequences_per_s"
HERE = Path(__file__).resolve().parent


def _reference():
    path = HERE.parent / "configs" / "toy_reference.py"
    spec = importlib.util.spec_from_file_location("toy_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _loss(params, tokens, labels):
    x = jnp.mean(params["embed"][tokens], axis=1)
    logits = jax.nn.relu(x @ params["up"]) @ params["down"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def program(cell, mesh):
    m, o = cell.model["model"], cell.model["optimizer"]
    params = {"embed": jnp.zeros((m["vocab"], m["d_model"])),
              "up": jnp.zeros((m["d_model"], m["d_ff"])),
              "down": jnp.zeros((m["d_ff"], m["classes"]))}
    opt = {"m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params),
           "step": jnp.zeros((), jnp.int32)}

    def step(params, state, opt, tokens, labels):
        loss, g = jax.value_and_grad(_loss)(params, tokens, labels)
        t = opt["step"] + 1
        mo = jax.tree.map(lambda a, b: o["beta1"] * a + (1 - o["beta1"]) * b,
                          opt["m"], g)
        v = jax.tree.map(
            lambda a, b: o["beta2"] * a + (1 - o["beta2"]) * b * b,
            opt["v"], g)
        tf = t.astype(jnp.float32)
        c1, c2 = 1 - o["beta1"] ** tf, 1 - o["beta2"] ** tf
        params = jax.tree.map(
            lambda p, a, b: p - o["lr"] * (a / c1) / (jnp.sqrt(b / c2)
                                                      + o["eps"]),
            params, mo, v)
        metrics = {"loss": loss, "nonfinite": jnp.zeros((), jnp.float32)}
        return params, state, {"m": mo, "v": v, "step": t}, metrics

    return (params, {}, opt), step, {}


class Tokens:
    def __init__(self, mix, model, seed):
        self.samples = int(mix["batch"])
        self.m = model["model"]
        self.seed = [seed % 2**32, seed // 2**32]

    def batch(self, step):
        rng = np.random.default_rng(self.seed + [step])
        b, m = self.samples, self.m
        return {"tokens": rng.integers(0, m["vocab"], (b, m["seq"]),
                                       dtype=np.int32),
                "labels": rng.integers(0, m["classes"], b, dtype=np.int32)}

    @staticmethod
    def inputs(placed):
        return placed["tokens"], placed["labels"]


def traffic(mix, model, seed):
    return Tokens(mix, model, seed)


def init(name, shape, dtype, key):
    return jax.random.normal(key, shape, dtype) * shape[0] ** -0.5


def reference_step(model, dtype=jnp.float32):
    return _reference().make_step(model, dtype)


def reference_state(structs):
    return structs


def stats(state, model):
    return None


def depth_stacked(name):
    return False


def step_flops(model, batch):
    m = model["model"]
    return 6.0 * batch * (m["d_model"] * m["d_ff"] + m["d_ff"] * m["classes"])


def kernel_work(kind, model, batch, plan):
    return []
'''

REFERENCE = '''
"""The toy's plain reference: one-hot embedding products, Adam, all in
``dtype``."""
import jax
import jax.numpy as jnp


def make_step(model, dtype=jnp.float32):
    m, o = model["model"], model["optimizer"]

    def loss_of(p, tokens, labels):
        onehot = jax.nn.one_hot(tokens, m["vocab"], dtype=dtype)
        x = jnp.einsum("bsv,vd->bd", onehot, p["embed"]) / m["seq"]
        h = jnp.maximum(x @ p["up"], 0)
        z = h @ p["down"]
        z = z - jnp.max(z, axis=1, keepdims=True)
        logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=1, keepdims=True))
        pick = jax.nn.one_hot(labels, m["classes"], dtype=dtype)
        return -jnp.sum(logp * pick) / tokens.shape[0]

    def step(params, state, opt, tokens, labels):
        cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
        p, mo, v = cast(params), cast(opt["m"]), cast(opt["v"])
        loss, g = jax.value_and_grad(loss_of)(p, tokens, labels)
        t = opt["step"] + 1
        tf = t.astype(dtype)
        new_p, new_m, new_v = {}, {}, {}
        for k in p:
            new_m[k] = o["beta1"] * mo[k] + (1 - o["beta1"]) * g[k]
            new_v[k] = o["beta2"] * v[k] + (1 - o["beta2"]) * g[k] ** 2
            u = (new_m[k] / (1 - o["beta1"] ** tf)) / (
                jnp.sqrt(new_v[k] / (1 - o["beta2"] ** tf)) + o["eps"])
            new_p[k] = p[k] - o["lr"] * u
        back = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
        metrics = {"loss": loss.astype(jnp.float32),
                   "nonfinite": jnp.zeros((), jnp.float32)}
        return (back(new_p), state,
                {"m": back(new_m), "v": back(new_v), "step": t}, metrics)

    return step
'''


def write_root(root, family_name="toy"):
    """A benchmark root holding the toy family and nothing of the
    Spikingformer."""
    bench = root / "bench"
    for d in ("configs", "families", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(BENCHMARK)
    (bench / "configs" / "toy-mlp.json").write_text(
        CONFIG.replace('"toy"', f'"{family_name}"') if family_name
        else CONFIG.replace('"family": "toy",', ""))
    (bench / "configs" / "toy_reference.py").write_text(
        textwrap.dedent(REFERENCE))
    (bench / "families" / "toy.py").write_text(textwrap.dedent(FAMILY))
    (bench / "traffic" / "tokens.b16.json").write_text(MIX)
    (bench / "limits" / f"{CELL}.json").write_text(LIMITS)
    return root


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return cells.find_cell(CELL, write_root(tmp_path_factory.mktemp("toy")))


def run(cell, hook=None, trace=False):
    return harness.run(cell, 2**31 + 29, 0.3, trace, jax.devices()[:1],
                       PEAKS, time.perf_counter(), step_hook=hook)


@pytest.fixture(scope="module")
def sound(toy):
    return run(toy)


def test_a_toy_family_runs_correct_through_the_harness(toy, sound):
    assert toy.family.__name__ == "bench_family_toy"
    assert sound["correct"], sound["compared"]
    assert set(sound["metrics"]) == {"sequences_per_s", "setup_s"}
    assert sound["metrics"]["sequences_per_s"]["value"] > 0
    assert set(sound["compared"]) == {"loss_gap", "grad_gap", "change_gap"}


def test_a_family_without_statistics_reads_no_stats_gap(toy, sound):
    """The numbers the run reads, with every limit lifted: no stats_gap,
    and no NaN in its place."""
    import dataclasses

    from bench.compare import NUMBERS

    lifted = dataclasses.replace(toy, limits={k: math.inf for k in NUMBERS
                                              if k != "stats_gap"})
    out = run(lifted)
    assert "stats_gap" not in out["compared"]
    assert all(math.isfinite(v["value"]) for v in out["compared"].values())
    # a limit on a number the family does not read cannot be met
    held = dataclasses.replace(toy, limits=dict(toy.limits, stats_gap=1.0))
    assert not run(held)["correct"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "control"])
def test_the_toy_rejects_the_control_and_a_state_left_unchanged(toy, fault):
    hook = (faults.control(toy) if fault == "control"
            else getattr(faults, fault))
    out = run(toy, hook=hook)
    assert not out["correct"], out["compared"]


def test_a_traced_toy_run_reads_its_family_work(toy):
    """step_mfu counts the toy's FLOPs; the LIF roofline has nothing to
    read in a model without LIF kernels."""
    out = run(toy, trace=True)
    assert out["metrics"]["step_mfu"]["value"] > 0
    assert "lif_roofline" not in out["metrics"]


@pytest.mark.parametrize("family_name", ["no-such-family", None])
def test_an_unknown_or_missing_family_names_the_families(tmp_path,
                                                         family_name):
    root = write_root(tmp_path, family_name)
    with pytest.raises(KeyError, match=r"model family .*\['toy'\]"):
        cells.find_cell(CELL, root)


COMMON = sorted(p for p in BENCH.glob("*.py") if p.name != "traffic.py")
FAMILY_WORDS = re.compile(r"spikingformer|tokenizer|images", re.I)
PROGRAM_IMPORTS = re.compile(
    r"repro\.core\.spikingformer|repro\.launch\.train|spikingformer_reference")


@pytest.mark.parametrize("path", COMMON + [BENCH / "traffic.py"],
                         ids=lambda p: p.name)
def test_the_common_modules_know_no_model_family(path):
    """Only bench/families/ and bench/configs/ know the Spikingformer. The
    image generator traffic.py, which image families share, may speak of
    images, and imports nothing of the model."""
    text = path.read_text()
    assert not PROGRAM_IMPORTS.search(text)
    if path in COMMON:
        assert not FAMILY_WORDS.search(text), FAMILY_WORDS.search(text)
