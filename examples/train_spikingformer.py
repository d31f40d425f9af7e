"""End-to-end driver: train a ~1M-param Spikingformer with BPTT on a
learnable synthetic vision task for a few hundred steps, with AdamW,
cosine schedule, checkpointing and straggler monitoring.

The task: classify which quadrant of the image carries the brightest
Gaussian blob (the shared ``repro.train.data.SyntheticVision`` stream —
loss should fall well below ln(4) chance level within ~100 steps).

Run:  PYTHONPATH=src python examples/train_spikingformer.py [--steps 200]

For mesh-sharded multi-device training use the launch driver instead:
``python -m repro.launch.train --arch spikingformer-tiny`` (same model,
same train-step factory, plus FSDP + data/model sharding).
"""
import argparse
import os
import warnings

import jax
import numpy as np

from repro.configs.spikingformer import get_spikingformer_config
from repro.core.policy import list_named_policies, named_policy
from repro.core.spikingformer import init_spikingformer
from repro.launch.cache import enable_compile_cache
from repro.train.checkpoint import save_checkpoint
from repro.train.data import SyntheticVision, VisionDataConfig
from repro.train.loop import make_spikingformer_train_step
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.resilience import StragglerMonitor


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--policy", choices=list_named_policies(),
                    default=os.environ.get("REPRO_BACKEND", "jnp"),
                    help="execution policy: jnp (lax.scan reference), "
                         "pallas (fused SOMA/GRAD + BN kernels; interpret "
                         "mode off-TPU) or pallas-full (adds the bit-packed "
                         "spike matmuls and packed (QK^T)V attention)")
    ap.add_argument("--time-chunk", type=int, default=None,
                    help="temporal tile length for the BPTT scan (memory "
                         "scales with T/time_chunk; gradients are exact)")
    ap.add_argument("--spike-mm", action="store_true",
                    help="deprecated: use --policy pallas-full")
    args = ap.parse_args()
    enable_compile_cache()

    policy = named_policy(args.policy)
    if args.spike_mm:
        # One-release shim, same story as the config-kwarg deprecations:
        # accepted, warned about, folded into the policy spelling.
        warnings.warn("--spike-mm is deprecated; use --policy pallas-full "
                      "(see docs/EXECUTION.md)", DeprecationWarning,
                      stacklevel=1)
        policy = policy.with_sites({"linear_bn": "pallas+spike_mm"})
    cfg = get_spikingformer_config("spikingformer-tiny", policy=policy,
                                   time_chunk=args.time_chunk)
    print(f"spikingformer params: {cfg.param_count():,} "
          f"policy={args.policy} time_chunk={cfg.time_chunk}")
    print(cfg.describe_execution())
    params, state = init_spikingformer(jax.random.PRNGKey(0), cfg)
    opt_cfg = OptimizerConfig(lr=2e-3, warmup_steps=20,
                              total_steps=args.steps, weight_decay=0.01)
    opt_state = init_opt_state(params)
    train_step = make_spikingformer_train_step(cfg, opt_cfg)
    data = SyntheticVision(VisionDataConfig(
        image_size=cfg.image_size, num_classes=cfg.num_classes,
        global_batch=args.batch, channels=cfg.in_channels))
    monitor = StragglerMonitor()

    for step in range(args.steps):
        monitor.step_start()
        batch = data.batch(step)
        params, state, opt_state, metrics = train_step(
            params, state, opt_state, batch["images"], batch["labels"])
        monitor.step_end()
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                  f"acc {float(metrics['accuracy']):.2f} "
                  f"gnorm {float(metrics['grad_norm']):.2f}", flush=True)
        if args.ckpt_dir and (step + 1) % 100 == 0:
            save_checkpoint(args.ckpt_dir, step + 1,
                            {"params": params, "bn": state}, async_save=True)
    print(f"median step time {monitor.median * 1e3:.0f} ms "
          f"(chance loss = {np.log(4):.3f})")


if __name__ == "__main__":
    main()
