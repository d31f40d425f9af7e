"""End-to-end training driver for every model family.

Runs real steps on the available devices (CPU smoke / TPU slice alike):
builds the mesh, initializes sharded params + optimizer, streams the
synthetic data pipeline, checkpoints asynchronously, monitors stragglers,
and restarts from the latest checkpoint after preemption. The Spikingformer
vision path runs through the same machinery (mesh, FSDP, ``place_batch``,
checkpointing) as the LM path — one launch subsystem, one train-step
factory.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch spikingformer-tiny \
      --steps 100 --batch 16 --policy pallas --time-chunk 2
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.tracing import compile_counter, span
from repro.chaos import inject as chaos_inject
from repro.configs.registry import get_config, reduced
from repro.core.policy import log_fallbacks
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import (apply_fsdp, batch_axes, make_test_mesh,
                               sanitize_specs, use_mesh)
from repro.models.common import spec_is_leaf, split_tree
from repro.train import checkpoint as ckpt
from repro.train.data import (DataConfig, SyntheticLM, SyntheticVision,
                              VisionDataConfig, place_batch)
from repro.train.loop import make_train_step
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.resilience import (NonFiniteGuard, PreemptionGuard,
                                    StragglerMonitor)


def build_state(cfg, mesh, opt_cfg, seed: int = 0):
    """Init params + opt state directly into their shardings."""
    if cfg.family == "audio":
        from repro.models.encdec import init_encdec as init
    else:
        from repro.models.lm import init_lm as init

    specs_box = {}

    def make(key):
        params, specs = split_tree(init(key, cfg))
        specs_box["s"] = specs
        return params

    struct = jax.eval_shape(make, jax.random.PRNGKey(seed))
    specs = sanitize_specs(specs_box["s"], struct, mesh)
    specs = apply_fsdp(specs, struct, mesh)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s if s is not None else P()),
        specs, is_leaf=spec_is_leaf)
    with use_mesh(mesh):
        params = jax.jit(make, out_shardings=shardings)(
            jax.random.PRNGKey(seed))
    opt_state = init_opt_state(params)
    return params, opt_state, specs


def build_spikingformer_state(cfg, mesh, opt_cfg, seed: int = 0,
                              fsdp_min_elems: int = 1 << 20):
    """Init Spikingformer params + BN state + opt state into their mesh
    shardings (the vision twin of :func:`build_state`)."""
    from repro.core.spikingformer import init_spikingformer
    from repro.launch.specs import spikingformer_structs

    _, (p_specs, s_specs) = spikingformer_structs(cfg, mesh, fsdp_min_elems)
    to_shardings = lambda specs: jax.tree.map(  # noqa: E731
        lambda s: NamedSharding(mesh, s if s is not None else P()),
        specs, is_leaf=spec_is_leaf)
    with use_mesh(mesh):
        params, state = jax.jit(
            lambda k: init_spikingformer(k, cfg),
            out_shardings=(to_shardings(p_specs), to_shardings(s_specs)))(
            jax.random.PRNGKey(seed))
        opt_state = init_opt_state(params)
    return params, state, opt_state, (p_specs, s_specs)


def _drive(mesh, *, start: int, steps: int, step_once, save, log_line,
           log_every: int, ckpt_every: int, ckpt_dir: str | None,
           nonfinite_budget: int = 3, final_join_timeout: float = 120.0):
    """Shared driver scaffolding for every family: straggler monitor,
    preemption guard, non-finite skip budget, checkpoint cadence, and the
    final async-save join (the last write must land before a restart scans
    the checkpoint directory).

    ``step_once(step) -> metrics`` advances the caller's model state (held
    in a closure); ``save(step)`` persists it, returning the writer thread
    when asynchronous; ``log_line(step, metrics)`` formats the progress
    line. Returns the per-step loss history.

    The step factory's in-jit guard reports skipped steps via
    ``metrics["nonfinite"]``; more than ``nonfinite_budget`` consecutive
    skips raise ``NonFiniteBudgetExceeded``. A final writer still alive
    after ``final_join_timeout`` seconds raises
    ``ckpt.CheckpointWriteTimeout`` so orchestrators see a nonzero exit
    instead of a scrolled-past warning.

    Each step runs inside ``jax.profiler.StepTraceAnnotation("train",
    step_num=step)``, with the loss read in the host span ``train.sync``,
    so in a profiler trace every span of a step carries its step id. The
    straggler monitor times a step up to that read (device included). A
    step after the first that builds an executable prints
    ``[recompile] step N: k executables``.
    """
    monitor = StragglerMonitor(
        on_straggler=lambda dt, med: print(
            f"[straggler] step took {dt:.3f}s (median {med:.3f}s)"))
    guard = PreemptionGuard().install()
    nf_guard = NonFiniteGuard(budget=nonfinite_budget)
    history = []
    pending_save = None

    with use_mesh(mesh), compile_counter() as compiles:
        for step in range(start, steps):
            chaos_inject.step_fault(step)
            built = compiles()
            monitor.step_start()
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                metrics = step_once(step)
                with span("train.sync"):
                    loss = float(metrics["loss"])
            monitor.step_end()
            history.append(loss)
            if step > start and compiles() > built:
                print(f"[recompile] step {step}: {compiles() - built} "
                      f"executables", flush=True)
            if nf_guard.observe(float(metrics.get("nonfinite", 0.0)) > 0.0,
                                step):
                print(f"[guard] step {step} non-finite loss/grads — state "
                      f"unchanged, step skipped "
                      f"({nf_guard.consecutive}/{nf_guard.budget} "
                      f"consecutive)", flush=True)
            if step % log_every == 0 or step == steps - 1:
                print(log_line(step, metrics), flush=True)
            if ckpt_dir and ((step + 1) % ckpt_every == 0
                             or guard.requested):
                pending_save = save(step + 1)
                if guard.requested:
                    print("[preempt] checkpoint saved, exiting")
                    break
    if pending_save is not None:
        pending_save.join(timeout=final_join_timeout)
        if pending_save.is_alive():
            raise ckpt.CheckpointWriteTimeout(
                f"final async checkpoint write still running after "
                f"{final_join_timeout:.0f}s — the run's last state may not "
                f"be on disk; a restart would resume from an older step")
    return history


def train_vision(cfg, *, steps: int, global_batch: int,
                 ckpt_dir: str | None, mesh=None, microbatches: int = 1,
                 log_every: int = 10, ckpt_every: int = 100, seed: int = 0,
                 lr: float = 2e-3):
    """Mesh-sharded Spikingformer BPTT training (the vision twin of
    :func:`train`): batch shards over ("pod", "data"), projections/heads
    over "model", FSDP'd weights, synthetic quadrant-blob data through
    ``place_batch``, checkpointing (params + BN state + optimizer) with
    elastic restore."""
    mesh = mesh or make_test_mesh(jax.device_count(), 1)
    # The plan at this batch: capacity demotions are decided (and logged)
    # here, from shape, before anything compiles.
    log_fallbacks(cfg.execution_plan(global_batch))
    opt_cfg = OptimizerConfig(lr=lr, total_steps=steps, weight_decay=0.01,
                              warmup_steps=max(steps // 20, 5))
    params, state, opt_state, (p_specs, s_specs) = build_spikingformer_state(
        cfg, mesh, opt_cfg, seed)
    from repro.train.optimizer import init_opt_specs
    specs = {"params": p_specs, "state": s_specs,
             "opt": init_opt_specs(p_specs)}

    start = 0
    if ckpt_dir:
        tree = {"params": params, "state": state, "opt": opt_state}
        latest, restored = ckpt.restore_latest_good(ckpt_dir, tree, mesh,
                                                    specs)
        if latest is not None:
            print(f"[restore] step {latest} from {ckpt_dir}")
            params, state, opt_state = (restored["params"],
                                        restored["state"], restored["opt"])
            start = latest

    data = SyntheticVision(VisionDataConfig(
        image_size=cfg.image_size, num_classes=cfg.num_classes,
        global_batch=global_batch, channels=cfg.in_channels, seed=seed,
        spikes=cfg.spike_input))
    # microbatches != 1 raises in the factory (BN stats are per-global-batch)
    step_fn = make_train_step(cfg, opt_cfg, microbatches, mesh=mesh)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1, 2))

    def step_once(step):
        nonlocal params, state, opt_state
        batch = place_batch(
            chaos_inject.poison_batch(data.batch(step), step), mesh)
        params, state, opt_state, metrics = jit_step(
            params, state, opt_state, batch["images"], batch["labels"])
        return metrics

    def save(step):
        return ckpt.save_checkpoint(
            ckpt_dir, step,
            {"params": params, "state": state, "opt": opt_state},
            specs, async_save=True)

    def log_line(step, m):
        return (f"step {step:5d} loss {float(m['loss']):.4f} "
                f"acc {float(m['accuracy']):.2f} "
                f"gnorm {float(m['grad_norm']):.3f} "
                f"lr {float(m['lr']):.2e}")

    history = _drive(mesh, start=start, steps=steps, step_once=step_once,
                     save=save, log_line=log_line, log_every=log_every,
                     ckpt_every=ckpt_every, ckpt_dir=ckpt_dir)
    return params, history


def train(cfg, *, steps: int, global_batch: int, seq_len: int = 128,
          ckpt_dir: str | None = None, mesh=None, microbatches: int = 1,
          log_every: int = 10, ckpt_every: int = 100, seed: int = 0,
          data_vocab: int | None = None, lr: float | None = None):
    """Family dispatch: ``lr=None`` picks the per-family default (3e-4 LM,
    2e-3 for the small vision models)."""
    if getattr(cfg, "family", None) == "vision":
        return train_vision(cfg, steps=steps, global_batch=global_batch,
                            ckpt_dir=ckpt_dir, mesh=mesh,
                            microbatches=microbatches, log_every=log_every,
                            ckpt_every=ckpt_every, seed=seed,
                            lr=lr if lr is not None else 2e-3)
    mesh = mesh or make_test_mesh(jax.device_count(), 1)
    opt_cfg = OptimizerConfig(lr=lr if lr is not None else 3e-4,
                              total_steps=steps,
                              warmup_steps=max(steps // 20, 5))
    params, opt_state, specs = build_state(cfg, mesh, opt_cfg, seed)

    start = 0
    if ckpt_dir:
        latest, restored = ckpt.restore_latest_good(ckpt_dir, params, mesh,
                                                    specs)
        if latest is not None:
            print(f"[restore] step {latest} from {ckpt_dir}")
            params = restored
            start = latest

    data = SyntheticLM(DataConfig(
        vocab_size=data_vocab or cfg.vocab_size, seq_len=seq_len,
        global_batch=global_batch, seed=seed))
    step_fn = make_train_step(cfg, opt_cfg, microbatches)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    def step_once(step):
        nonlocal params, opt_state
        batch = place_batch(
            chaos_inject.poison_batch(data.batch(step), step), mesh)
        if cfg.family == "audio":
            bsz = batch["tokens"].shape[0]
            batch["frames"] = jnp.zeros(
                (bsz, cfg.encoder_seq, cfg.d_model), cfg.dtype)
        if cfg.vlm_stub:
            bsz, s = batch["tokens"].shape
            batch["patch_embeds"] = jnp.zeros((bsz, s, cfg.d_model),
                                              cfg.dtype)
            batch["patch_mask"] = jnp.zeros((bsz, s), bool)
        params, opt_state, metrics = jit_step(params, opt_state, batch)
        return metrics

    def save(step):
        return ckpt.save_checkpoint(ckpt_dir, step, params, specs,
                                    async_save=True)

    def log_line(step, m):
        return (f"step {step:5d} loss {float(m['loss']):.4f} "
                f"gnorm {float(m['grad_norm']):.3f} "
                f"lr {float(m['lr']):.2e}")

    history = _drive(mesh, start=start, steps=steps, step_once=step_once,
                     save=save, log_line=log_line, log_every=log_every,
                     ckpt_every=ckpt_every, ckpt_dir=ckpt_dir)
    return params, history


def _resolve_config(args):
    """LM/audio registry first; spikingformer preset names (optionally with
    an ``@<policy>`` suffix) route to the vision path. Flags that only
    exist for the other family are rejected, never silently dropped."""
    try:
        cfg = get_config(args.arch)
    except KeyError:
        from repro.configs.registry import list_configs
        from repro.configs.spikingformer import (get_spikingformer_config,
                                                 list_spikingformer_configs)
        from repro.core.policy import named_policy
        if args.reduced:
            raise SystemExit("--reduced applies to LM/audio archs only; "
                             "pick a smaller spikingformer preset instead")
        if args.data_vocab is not None or args.seq is not None:
            raise SystemExit("--data-vocab/--seq apply to LM/audio archs "
                             "only (the vision data stream is sized by the "
                             "preset's image_size/num_classes)")
        try:
            return get_spikingformer_config(
                args.arch,
                policy=named_policy(args.policy) if args.policy else None,
                time_chunk=args.time_chunk)
        except KeyError:
            raise SystemExit(
                f"unknown --arch {args.arch!r}; LM/audio: {list_configs()}; "
                f"vision: {list_spikingformer_configs()}") from None
    if args.policy or args.time_chunk:
        raise SystemExit("--policy/--time-chunk apply to spikingformer "
                         f"archs only, not {args.arch!r}")
    if args.reduced:
        cfg = reduced(cfg)
    return cfg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=None,
                    help="LM sequence length (default 128)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--data-vocab", type=int, default=None)
    ap.add_argument("--policy", default=None,
                    help="execution policy preset for spikingformer archs")
    ap.add_argument("--time-chunk", type=int, default=None,
                    help="temporal tile length for spikingformer BPTT")
    ap.add_argument("--chaos-schedule", default=None,
                    help="fault-injection schedule (JSON file or inline "
                         "JSON; also honored via $CHAOS_SCHEDULE). See "
                         "docs/RESILIENCE.md")
    args = ap.parse_args()
    enable_compile_cache()
    if args.chaos_schedule:
        from repro.chaos import FaultSchedule, activate
        import os as _os
        activate(FaultSchedule.from_file(args.chaos_schedule)
                 if _os.path.exists(args.chaos_schedule)
                 else FaultSchedule.from_json(args.chaos_schedule))
    else:
        chaos_inject.activate_from_env()
    cfg = _resolve_config(args)
    _, history = train(cfg, steps=args.steps, global_batch=args.batch,
                       seq_len=args.seq if args.seq is not None else 128,
                       ckpt_dir=args.ckpt_dir,
                       microbatches=args.microbatches,
                       data_vocab=args.data_vocab)
    print(f"final loss {history[-1]:.4f} (from {history[0]:.4f})")


if __name__ == "__main__":
    main()
