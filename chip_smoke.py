#!/usr/bin/env python3
"""Bring-up check: Spikingformer-8-512 BPTT training on one TPU chip.

    python3 chip_smoke.py               # one chip: jnp, pallas, pallas-full
    python3 chip_smoke.py --four-chips  # (data=4, model=1) mesh vs one chip
    python3 chip_diag.py                # the readings behind the bounds

Everything runs in this one process. In order:

1. device check — exits non-zero, naming the platform, unless JAX's first
   device is a TPU; nothing else runs;
2. compile cache — ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``;
3. batch — the largest of :data:`BATCH_CANDIDATES` whose train step, as the
   TPU compiler sizes it (``memory_analysis()``), fits every arm into
   :data:`HBM_SHARE` of the chip's memory;
4. kernels — every Pallas kernel of the train path against its
   ``repro.kernels.ref`` oracle, at the shapes its sites see at that batch;
5. composed — the tokenizer's and block 0's forward and backward passes,
   as the train step runs them, under each policy preset against ``jnp``,
   each module fed a fixed input and a fixed output cotangent so that no
   spike flipped upstream reaches it (:func:`composed_run`): outputs,
   parameter gradients and input gradients within :data:`COMPOSED_TOL`;
6. train — :data:`STEPS` steps of the paper preset through
   ``repro.launch.train.train_vision`` under each policy preset, printing
   the plan, the planned step bytes, losses and the step-0 loss
   difference from ``jnp``; fails on a non-finite loss, a circuit-breaker
   trip, a WARNING from the execution policy, a call-time fallback the
   plan did not report, or a step-0 loss more than :data:`LOSS0_TOL` from
   ``jnp``'s.

The last line of standard output is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``; a failed run
prints no verdict and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import logging
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PRESET = "spikingformer-8-512"
ARMS = ("jnp", "pallas", "pallas-full")
STEPS = 3
#: Global batches tried, largest first. The paper's 16 is left out: the
#: jnp arm's step alone plans ~20 GiB there.
BATCH_CANDIDATES = (8, 4, 2)
#: Share of the chip's memory one train step may plan to use.
HBM_SHARE = 0.9
#: Largest |step-0 loss - jnp's step-0 loss|: a sanity bound, not the
#: correctness check. TPU matmuls round differently inside Pallas kernels
#: and in XLA, and a membrane potential that lands on the other side of a
#: spike threshold changes every spike train downstream of it, through 8
#: blocks. On a v5e at batch 8, 1e-6 input noise or XLA's matmul precision
#: alone move the jnp arm's step-0 loss by up to about 0.1
#: (``chip_diag.py``), so the loss cannot tell rounding from a fault. The
#: composed check (:data:`COMPOSED_TOL`) and the kernel check
#: (:data:`KERNEL_TOL`) are the ones that can fail.
LOSS0_TOL = 0.5
#: Largest relative gap ||got - want|| / ||want|| of any leaf of
#: :func:`composed_run` between two programs of the same passes. Each
#: module holds only a few spike thresholds between its fixed input and
#: its output, so rounding moves few spikes: on a v5e at batch 8 the
#: Pallas arms read at most 0.08 against jnp. A dropped or mis-wired term
#: moves a whole leaf: the BN faults ``chip_diag.py`` plants read 1.0 and
#: more.
COMPOSED_TOL = 0.3


def check_device():
    """The first JAX device, if it is a TPU; otherwise exit non-zero."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU but JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind}); no phase ran")
    return dev


def _import_repro():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def arm_config(arm: str, preset: str = PRESET):
    from repro.configs.spikingformer import get_spikingformer_config
    from repro.core.policy import named_policy

    return get_spikingformer_config(preset, policy=named_policy(arm))


def _rel_err(got, want) -> float:
    """max |got - want| over max |want|."""
    import jax.numpy as jnp

    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def _mismatch(got, want) -> float:
    """Share of {0,1} spikes that differ."""
    import jax.numpy as jnp

    return float(jnp.mean(jnp.asarray(got) != jnp.asarray(want)))


def _binary(x) -> bool:
    import jax.numpy as jnp

    return bool(jnp.all((x == 0) | (x == 1)))


#: Kernel-vs-oracle bounds: elementwise and reduction kernels agree to
#: fp32 rounding; a matmul inside a kernel may round its fp32 operands to
#: bf16 (2^-8) where the oracle runs at full precision; a spike output may
#: flip where that rounding moves a membrane potential across a threshold.
KERNEL_TOL = {"exact": 1e-4, "matmul": 1e-2, "spikes": 1e-2}


def kernel_cases(cfg, batch: int):
    """(name, kernel thunk, oracle thunk, how to compare) for every Pallas
    kernel of the train path, at the shapes its sites see at ``batch``.
    The neuron-layer train arm runs at the largest batch <= ``batch`` at
    which the plan keeps it at ``pssa.qkv``."""
    import jax
    import jax.numpy as jnp

    from repro.core.spikingformer import fused_site_geometries
    from repro.kernels import (conv_spike, fused_bn, lif_soma, neuron_layer,
                               ref, spike_matmul)

    geo = fused_site_geometries(cfg, batch)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))
    normal = lambda *s: jax.random.normal(next(keys), s)  # noqa: E731
    spikes = lambda *s: jax.random.bernoulli(  # noqa: E731
        next(keys), 0.3, s).astype(jnp.float32)
    t, m, d, f = *geo["smlp.a"][:2], cfg.d_model, cfg.d_ff
    heads, n = cfg.n_heads, cfg.num_tokens
    cases = []

    x = normal(t, m, f) + 0.5
    cases.append(("lif_soma_fwd", lambda: lif_soma.lif_soma_fwd(x),
                  lambda: ref.lif_soma_fwd_ref(x), "spikes"))
    u, s, mask = ref.lif_soma_fwd_ref(x)
    g = normal(t, m, f)
    cases.append(("lif_soma_bwd", lambda: lif_soma.lif_soma_bwd(g, u, s, mask),
                  lambda: ref.lif_soma_bwd_ref(g, u, s, mask), "exact"))
    t0, m0, _, k0 = geo["tokenizer.conv.0"]
    for name, rows, width in (("linear", t * m, d), ("tokenizer.0", t0 * m0, k0)):
        xb, gb = normal(rows, width) * 2 + 0.5, normal(rows, width)
        gamma, beta = normal(width) + 1.0, normal(width)
        _, mu, sq = ref.bn_fwd_ref(xb, gamma, beta)
        cases.append((f"bn_fwd[{name}]",
                      lambda xb=xb, gamma=gamma, beta=beta:
                      fused_bn.bn_fwd(xb, gamma, beta),
                      lambda xb=xb, gamma=gamma, beta=beta:
                      ref.bn_fwd_ref(xb, gamma, beta), "exact"))
        cases.append((f"bn_bwd[{name}]",
                      lambda xb=xb, gb=gb, gamma=gamma, mu=mu, sq=sq:
                      fused_bn.bn_bwd(gb, xb, gamma, mu, sq),
                      lambda xb=xb, gb=gb, gamma=gamma, mu=mu, sq=sq:
                      ref.bn_bwd_ref(gb, xb, gamma, mu, sq), "exact"))
    sp, w = spikes(t * m, f), normal(f, d) * f ** -0.5
    cases.append(("spike_matmul[smlp.b]",
                  lambda: spike_matmul.spike_matmul(sp, w),
                  lambda: ref.spike_matmul_ref(sp, w), "matmul"))
    q = spikes(t * batch * heads, n, d // heads)
    kt = spikes(t * batch * heads, d // heads, n)
    cases.append(("spike_matmul_batched[attn_qk]",
                  lambda: spike_matmul.spike_matmul_batched(q, kt),
                  lambda: ref.spike_matmul_batched_ref(q, kt), "matmul"))
    t1, m1, c1, k1 = geo["tokenizer.conv.1"]
    pt, wt = spikes(t1, m1, c1), normal(c1, k1) * c1 ** -0.5
    cases.append(("spike_patch_matmul[tokenizer.1]",
                  lambda: conv_spike.spike_patch_matmul(pt, wt),
                  lambda: ref.spike_patch_matmul_ref(pt, wt), "matmul"))
    fits = [b for b in range(batch, 0, -1)
            if neuron_layer.train_arm_vmem_bytes(
                *fused_site_geometries(cfg, b)["pssa.qkv"], packed=True)
            <= neuron_layer.TRAIN_ARM_VMEM_BUDGET]
    tq, mq, cq, kq = fused_site_geometries(cfg, fits[0])["pssa.qkv"]
    xq, wq = spikes(tq, mq, cq), normal(cq, kq) * cq ** -0.5 * 4
    gq, bq = normal(kq) + 1.0, normal(kq)
    cases.append((f"neuron_layer_train[pssa.qkv@batch{fits[0]}]",
                  lambda: neuron_layer.neuron_layer_train(
                      xq, wq, gq, bq, packed=True),
                  lambda: ref.neuron_layer_train_ref(xq, wq, gq, bq),
                  "spikes"))
    xe = spikes(t, m, d)
    we, be = normal(d, d) * d ** -0.5 * 4, normal(d)
    cases.append(("neuron_layer_eval[pssa.qkv]",
                  lambda: neuron_layer.neuron_layer_eval(xe, we, be,
                                                         packed=True),
                  lambda: ref.neuron_layer_eval_ref(xe, we, be), "spikes"))
    return cases


def check_kernels(cfg, batch: int) -> list[str]:
    """Run every case of :func:`kernel_cases`; the oracles run at full
    fp32 matmul precision. Returns the failures."""
    import jax

    fails = []
    for name, kernel, oracle, how in kernel_cases(cfg, batch):
        got = jax.tree.leaves(kernel())
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(oracle())
        errs = [_mismatch(a, b) if how == "spikes" and _binary(b)
                else _rel_err(a, b) for a, b in zip(got, want)]
        tol = KERNEL_TOL[how]
        print(f"[kernel] {name}: {how} errors {errs} (bound {tol})",
              flush=True)
        if not all(e <= tol for e in errs):
            fails.append(f"{name}: {errs} > {tol}")
    return fails


#: The parameter gradients of :func:`composed_run`: sums over the batch,
#: so each needs the reduction across chips that a data-parallel step does.
PARAM_GRADS = ("tokenizer.grad", "block.grad")


def composed_run(cfg, batch: int, mesh, keep: int | None = None) -> dict:
    """Forward and backward passes of the tokenizer and of block 0 as the
    train step runs them (``train=True``, weights from seed 0 placed as
    ``train_vision`` places them on ``mesh``, batch 0 of its data). Block
    0 takes fixed random spikes instead of the tokenizer's output, and
    each module's output gets a fixed random cotangent, so a spike flipped
    by rounding in one module cannot reach the other. ``keep`` runs only
    the first ``keep`` examples. Returns host copies of the outputs, the
    parameter gradients and block 0's input gradient, by name."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.spiking_layers import block_apply
    from repro.core.spikingformer import tokenizer_apply
    from repro.launch.mesh import batch_axes, use_mesh
    from repro.launch.train import build_spikingformer_state
    from repro.train.data import SyntheticVision, VisionDataConfig
    from repro.train.optimizer import OptimizerConfig

    params, state, _, _ = build_spikingformer_state(cfg, mesh,
                                                    OptimizerConfig())
    first = lambda tree: jax.tree.map(lambda a: a[0], tree)  # noqa: E731
    images = SyntheticVision(VisionDataConfig(
        image_size=cfg.image_size, num_classes=cfg.num_classes,
        global_batch=batch, channels=cfg.in_channels,
        spikes=cfg.spike_input)).batch(0)["images"]
    rng = np.random.default_rng(1)
    shape = (cfg.time_steps, batch, cfg.num_tokens, cfg.d_model)
    tokens = (rng.random(shape) < 0.25).astype(np.float32)
    cts = [rng.standard_normal(shape, dtype=np.float32) for _ in range(2)]
    keep = batch if keep is None else keep

    def place(a, axis):
        a = np.take(a, np.arange(keep), axis=axis)
        spec = [None] * a.ndim
        spec[axis] = batch_axes(mesh)
        return jax.device_put(a, NamedSharding(mesh, P(*spec)))

    def passes(p_tok, s_tok, p_blk, s_blk, images, tokens, ct_tok, ct_blk):
        images = jnp.broadcast_to(images[None],
                                  (cfg.time_steps,) + images.shape)
        tok, tok_vjp = jax.vjp(lambda p: tokenizer_apply(
            p, s_tok, images, cfg, train=True)[0], p_tok)
        blk, blk_vjp = jax.vjp(lambda p, x: block_apply(
            p, s_blk, x, cfg.block, train=True)[0], p_blk, tokens)
        g_blk, g_x = blk_vjp(ct_blk)
        return {"tokenizer.out": tok, "tokenizer.grad": tok_vjp(ct_tok)[0],
                "block.out": blk, "block.grad": g_blk, "block.dx": g_x}

    with use_mesh(mesh):
        out = jax.jit(passes)(
            params["tokenizer"], state["tokenizer"], first(params["blocks"]),
            first(state["blocks"]), place(images, 0), place(tokens, 1),
            place(cts[0], 1), place(cts[1], 1))
    return jax.device_get(out)


def composed_gaps(got: dict, want: dict) -> dict[str, float]:
    """For each quantity of ``got`` (see :func:`composed_run`), the largest
    relative gap ||got - want|| / ||want|| over its leaves."""
    import jax
    import numpy as np

    def gap(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    return {name: max(gap(a, b) for a, b in zip(jax.tree.leaves(tree),
                                                jax.tree.leaves(want[name])))
            for name, tree in got.items()}


def _gap_failures(what: str, gaps: dict[str, float]) -> list[str]:
    return [f"{what}: {name} gap {g!r} > {COMPOSED_TOL}"
            for name, g in gaps.items() if not g <= COMPOSED_TOL]


def step_bytes(cfg, batch: int, device) -> int:
    """Device bytes the TPU compiler plans for one train step of ``cfg`` at
    global ``batch``: arguments + outputs - donated aliases + temporaries.
    ``device`` may be a real device or one of a described topology; only
    shapes are used, nothing is allocated."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.core.spikingformer import init_spikingformer
    from repro.train.loop import make_train_step
    from repro.train.optimizer import OptimizerConfig, init_opt_state

    on = SingleDeviceSharding(device)
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on), tree)
    params, state = jax.eval_shape(lambda k: init_spikingformer(k, cfg),
                                   jax.random.PRNGKey(0))
    opt = jax.eval_shape(init_opt_state, params)
    size = cfg.image_size
    images = jax.ShapeDtypeStruct((batch, size, size, cfg.in_channels),
                                  jnp.float32, sharding=on)
    labels = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=on)
    step = make_train_step(cfg, OptimizerConfig(total_steps=STEPS))
    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        place(params), place(state), place(opt), images, labels).compile()
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def choose_batch(configs, device, capacity: int,
                 candidates=BATCH_CANDIDATES) -> tuple[int, dict[str, int]]:
    """The largest candidate batch at which every config's train step fits
    into ``HBM_SHARE * capacity`` bytes, and each config's planned bytes
    there."""
    budget = int(HBM_SHARE * capacity)
    for batch in candidates:
        planned = {}
        for arm, cfg in configs.items():
            planned[arm] = need = step_bytes(cfg, batch, device)
            print(f"[batch] {batch} {arm}: planned {need} bytes of "
                  f"{budget}", flush=True)
            if need > budget:
                break
        else:
            return batch, planned
    raise SystemExit(f"chip_smoke: no batch in {candidates} fits "
                     f"{budget} bytes")


_compile_s = [0.0]


def _count_compile(event: str, duration: float, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


class PolicyRecords(logging.Handler):
    """Collects the execution policy's log records while installed (a
    context manager), INFO included."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.records: list[logging.LogRecord] = []

    def __enter__(self):
        from repro.core.policy import logger

        self._prev = logger.level
        logger.addHandler(self)
        logger.setLevel(logging.INFO)
        return self

    def __exit__(self, *exc):
        from repro.core.policy import logger

        logger.removeHandler(self)
        logger.setLevel(self._prev)

    def emit(self, record):
        self.records.append(record)

    def failures(self, plans) -> list[str]:
        """A WARNING, or a call-time decision no plan row reports."""
        noted = {r.site for plan in plans for r in plan if r.note}
        fails = []
        for rec in self.records:
            msg = rec.getMessage()
            if rec.levelno >= logging.WARNING:
                fails.append(f"WARNING from the execution policy: {msg}")
            elif "fell back at call time" in msg and rec.args[0] not in noted:
                fails.append(f"call-time decision no plan reports: {msg}")
        return fails


def train_arm(cfg, batch: int, steps: int = STEPS, mesh=None) -> dict:
    """Run ``train_vision``; return the loss history, the circuit
    breaker's trips, the wall time and the part of it XLA spent
    compiling."""
    from repro.core.policy import breaker_trips, reset_breaker
    from repro.launch.train import train_vision

    reset_breaker()
    t0, c0 = time.perf_counter(), _compile_s[0]
    _, history = train_vision(cfg, steps=steps, global_batch=batch,
                              ckpt_dir=None, mesh=mesh, log_every=1)
    return {"losses": history, "trips": breaker_trips(),
            "wall_s": time.perf_counter() - t0,
            "compile_s": _compile_s[0] - c0}


def arm_failures(cfg, batch: int, run: dict) -> list[str]:
    """A non-finite loss, a breaker trip, or a plan row that records a
    violated constraint rather than a planned decision."""
    fails = []
    if not all(math.isfinite(x) for x in run["losses"]):
        fails.append(f"non-finite loss in {run['losses']}")
    if run["trips"]:
        fails.append(f"circuit breaker tripped: {sorted(run['trips'])}")
    fails += [f"plan violates a constraint at {r.site}: {r.note}"
              for r in cfg.execution_plan(batch) if r.note and not r.expected]
    return fails


def peak_bytes(device) -> int | None:
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def _run_line(name: str, batch: int, run: dict, devices) -> str:
    return (f"[{name}] batch {batch} losses {run['losses']} wall_s "
            f"{run['wall_s']!r} compile_s {run['compile_s']!r} "
            f"process_peak_bytes_in_use "
            f"{[peak_bytes(d) for d in devices]}")


def one_chip(device) -> list[str]:
    """Batch sizing, kernel checks and the three arms; returns failures.
    ``process_peak_bytes_in_use`` is the device's peak since the process
    started, so an arm's own bytes are the planned ones."""
    from repro.launch.mesh import make_test_mesh

    configs = {arm: arm_config(arm) for arm in ARMS}
    capacity = device.memory_stats()["bytes_limit"]
    mesh = make_test_mesh(1, 1, devices=[device])
    with PolicyRecords() as records:
        batch, planned = choose_batch(configs, device, capacity)
        failures = [f"kernel {f}" for f in check_kernels(configs["jnp"],
                                                         batch)]
        loss0, passes = {}, {}
        for arm, cfg in configs.items():
            print(f"\n=== arm {arm}: {PRESET} batch {batch} ===", flush=True)
            print(cfg.describe_execution(batch=batch), flush=True)
            passes[arm] = composed_run(cfg, batch, mesh)
            gaps = composed_gaps(passes[arm], passes["jnp"])
            print(f"[composed] {arm} vs jnp: {gaps} (bound {COMPOSED_TOL})",
                  flush=True)
            fails = _gap_failures("composed vs jnp", gaps)
            run = train_arm(cfg, batch)
            loss0[arm] = run["losses"][0]
            diff = abs(loss0[arm] - loss0["jnp"])
            print(_run_line(arm, batch, run, [device])
                  + f" planned_step_bytes {planned[arm]}"
                  f" step0_diff_vs_jnp {diff!r}", flush=True)
            fails += arm_failures(cfg, batch, run)
            if not diff <= LOSS0_TOL:
                fails.append(f"step-0 loss {loss0[arm]!r} differs from "
                             f"jnp's {loss0['jnp']!r} by {diff!r} > "
                             f"{LOSS0_TOL}")
            failures += [f"{arm}: {f}" for f in fails]
    plans = [cfg.execution_plan(batch) for cfg in configs.values()]
    return failures + records.failures(plans)


def sharding_failures(cfg, batch: int, devices) -> list[str]:
    """:func:`composed_run` on a (data=len(devices), model=1) mesh against
    the same passes on the first device alone, within
    :data:`COMPOSED_TOL`. Also runs that device over only its share of the
    batch: its parameter gradients are what every chip would hold if the
    gradient and BN-statistic reductions between chips were dropped, and
    they must lie outside the bound, or the check could not see it."""
    from repro.launch.mesh import make_test_mesh

    single_mesh = make_test_mesh(1, 1, devices=devices[:1])
    single = composed_run(cfg, batch, single_mesh)
    sharded = composed_run(cfg, batch, make_test_mesh(len(devices), 1,
                                                      devices=devices))
    local = composed_run(cfg, batch, single_mesh,
                         keep=batch // len(devices))
    gaps = composed_gaps(sharded, single)
    dropped = composed_gaps({k: local[k] for k in PARAM_GRADS}, single)
    print(f"[composed] sharded vs single: {gaps} (bound {COMPOSED_TOL})\n"
          f"[composed] one shard's gradients vs single (reductions "
          f"dropped): {dropped}", flush=True)
    return _gap_failures("sharded vs single", gaps) + [
        f"a dropped reduction stays within the bound: {name} gap {g!r}"
        for name, g in dropped.items() if g <= COMPOSED_TOL]


def four_chips(devices) -> list[str]:
    """The jnp arm on a (data=4, model=1) mesh against one of the devices,
    from the same weights and batches: :func:`sharding_failures`, then
    ``train_vision``'s per-step losses (step 0 within :data:`LOSS0_TOL`;
    later steps follow differently rounded updates, so their gaps are
    printed, not bounded) and each device's peak memory, which shows how
    far parameters and batch were spread. Returns failures."""
    from repro.launch.mesh import make_test_mesh

    if len(devices) != 4:
        return [f"--four-chips needs 4 devices, found {len(devices)}"]
    cfg = arm_config("jnp")
    runs = {}
    with PolicyRecords() as records:
        capacity = devices[0].memory_stats()["bytes_limit"]
        batch, _ = choose_batch({"jnp": cfg}, devices[0], capacity)
        failures = sharding_failures(cfg, batch, devices)
        for name, devs in (("sharded", devices), ("single", devices[:1])):
            mesh = make_test_mesh(len(devs), 1, devices=devs)
            print(f"\n=== {name}: mesh {dict(mesh.shape)} ===", flush=True)
            runs[name] = run = train_arm(cfg, batch, mesh=mesh)
            print(_run_line(name, batch, run, devices), flush=True)
            failures += [f"{name}: {f}" for f in arm_failures(cfg, batch, run)]
    diffs = [abs(a - b) for a, b in zip(runs["sharded"]["losses"],
                                        runs["single"]["losses"])]
    print(f"[four-chips] per-step |sharded - single| {diffs}", flush=True)
    if not diffs[0] <= LOSS0_TOL:
        failures.append(f"sharded step-0 loss differs from one chip's by "
                        f"{diffs[0]!r} > {LOSS0_TOL}")
    return failures + records.failures([cfg.execution_plan(batch)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh vs one-chip comparison")
    args = ap.parse_args(argv)
    dev = check_device()
    _import_repro()
    import jax

    from repro.launch.cache import enable_compile_cache

    print(f"[cache] {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    devices = jax.devices()
    failures = four_chips(devices) if args.four_chips else one_chip(dev)
    if failures:
        print("chip_smoke: FAILED\n" + "\n".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
