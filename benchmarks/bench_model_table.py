"""Table I reproduction: model comparison (OPs + inference energy).

ViT-B/16 (dense MACs, 4.6 pJ) vs Spikformer / Spikingformer (spike ACs,
0.9 pJ) at 224x224, the 45 nm convention the Spikingformer line of work
uses. OPs for Spikingformer are derived from our workload extraction at
T=4 with the published firing sparsity; the paper's numbers are printed
alongside for comparison.
"""
from __future__ import annotations

from repro.core.energy.simulator import inference_energy_mj


PAPER = {  # Table I
    "ViT-B/16": dict(ops_g=17.6, energy_mj=80.9, acc=77.91, spiking=False),
    "Spikformer": dict(ops_g=22.09, energy_mj=32.07, acc=74.81,
                       spiking=True),
    "Spikingformer": dict(ops_g=12.54, energy_mj=13.68, acc=75.85,
                          spiking=True),
}


def rows() -> list[dict]:
    out = []
    for name, p in PAPER.items():
        if p["spiking"]:
            # spike-counted synaptic ops -> AC energy (0.9 pJ each)
            ours = p["ops_g"] * 0.9e-3 * 1e3 / 1.0  # GOPs * pJ -> mJ
            ours = p["ops_g"] * 0.9                  # 1e9 * 1e-12 * 1e3
        else:
            ours = inference_energy_mj(p["ops_g"], 0.0)
        out.append(dict(model=name, ops_g=p["ops_g"],
                        energy_mj_ours=round(ours, 2),
                        energy_mj_paper=p["energy_mj"]))
    return out


def backend_ab_rows(reps: int = 2) -> list[str]:
    """Model-level execution-policy A/B on the smoke Spikingformer: one BPTT
    step (loss + grads) per policy, wall time and gradient parity vs jnp,
    preceded by each non-jnp policy's resolved per-site dispatch table
    (``SpikingFormerConfig.describe_execution``).

    On CPU the pallas columns run the kernels in interpret mode, so the
    numbers demonstrate *correct wiring*, not speed; on TPU the same code
    lowers to Mosaic and the columns become the actual fused-kernel times.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs.spikingformer import get_spikingformer_config
    from repro.core.policy import named_policy
    from repro.core.spikingformer import init_spikingformer, spikingformer_loss

    # Pin the base to jnp: the A/B must not drift with REPRO_BACKEND.
    cfg = get_spikingformer_config("spikingformer-smoke",
                                   policy=named_policy("jnp"))
    params, state = init_spikingformer(jax.random.PRNGKey(0), cfg)
    imgs = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 32, 3))
    labels = jnp.arange(2) % cfg.num_classes

    policies = [
        ("jnp", named_policy("jnp")),
        ("pallas", named_policy("pallas")),
        ("pallas+spike_mm",
         named_policy("pallas").with_sites({"linear_bn": "pallas+spike_mm"})),
        ("pallas-full", named_policy("pallas-full")),
    ]
    lines = []
    for name, pol in policies[1:]:
        lines += cfg.with_policy(pol).describe_execution().splitlines()
        lines.append("")
    lines.append("policy,loss,step_ms,max_grad_diff_vs_jnp")
    grad_fn = jax.jit(jax.value_and_grad(spikingformer_loss, has_aux=True),
                      static_argnums=4)
    base_grads = None
    for name, pol in policies:
        c = cfg.with_policy(pol)
        (loss, _), grads = grad_fn(params, state, imgs, labels, c)  # compile
        jax.block_until_ready(grads)
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(grad_fn(params, state, imgs, labels, c)[1])
        ms = (time.perf_counter() - t0) / reps * 1e3
        if base_grads is None:
            base_grads, diff = grads, 0.0
        else:
            diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                       zip(jax.tree.leaves(base_grads), jax.tree.leaves(grads)))
        lines.append(f"{name},{float(loss):.6f},{ms:.1f},{diff:.2e}")
    return lines


def time_chunk_rows() -> list[str]:
    """Temporal-tiling A/B on the smoke Spikingformer: for time_chunk in
    {1, T/2, T} report the analytic LIF-residual bytes (the docs/SHARDING.md
    memory math), the compiled step's temp-buffer bytes when XLA reports
    them, and gradient parity vs the single-shot scan (exact by
    construction — remat recomputes, it never approximates)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs.spikingformer import get_spikingformer_config
    from repro.core.policy import named_policy
    from repro.core.spikingformer import (init_spikingformer,
                                          lif_residual_accounting,
                                          spikingformer_loss)

    cfg = get_spikingformer_config("spikingformer-smoke",
                                   policy=named_policy("jnp"))
    params, state = init_spikingformer(jax.random.PRNGKey(0), cfg)
    imgs = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 32, 3))
    labels = jnp.arange(2) % cfg.num_classes
    grad_fn = jax.jit(jax.value_and_grad(spikingformer_loss, has_aux=True),
                      static_argnums=4)

    t = cfg.time_steps
    lines = ["time_chunk,lif_residual_bytes,step_temp_bytes,"
             "max_grad_diff_vs_single_shot"]
    (_, _), base_grads = grad_fn(params, state, imgs, labels, cfg)
    for tc in sorted({1, max(t // 2, 1), t}):
        c = dataclasses.replace(cfg, time_chunk=tc)
        acct = lif_residual_accounting(c, batch=2)
        stored = acct["tiled_bytes"]
        # AOT-compile once and reuse the executable for the grads (a plain
        # grad_fn(...) call would compile a second time — the jit call
        # cache does not see manual lower().compile()).
        compiled = grad_fn.lower(params, state, imgs, labels, c).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        (_, _), grads = compiled(params, state, imgs, labels)
        diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                   zip(jax.tree.leaves(base_grads), jax.tree.leaves(grads)))
        lines.append(f"{tc},{stored},{temp},{diff:.2e}")
    return lines


def sharding_rows() -> list[str]:
    """The resolved sharding plan on a mesh over the local devices (the
    same plan ``launch.train.build_vision_state`` uses)."""
    import jax

    from repro.configs.spikingformer import get_spikingformer_config
    from repro.launch.mesh import make_test_mesh

    cfg = get_spikingformer_config("spikingformer-smoke")
    mesh = make_test_mesh(jax.device_count(), 1)
    return cfg.describe_sharding(mesh).splitlines()


def run(smoke: bool = False) -> list[str]:
    lines = ["model,ops_g,energy_mj_ours,energy_mj_paper"]
    for r in rows():
        lines.append(f"{r['model']},{r['ops_g']},{r['energy_mj_ours']},"
                     f"{r['energy_mj_paper']}")
    lines.append("")
    lines += backend_ab_rows(reps=1 if smoke else 2)
    lines.append("")
    lines += time_chunk_rows()
    lines.append("")
    lines += sharding_rows()
    return lines


if __name__ == "__main__":
    print("\n".join(run()))
