"""Static execution-plan auditor: the plan that runs is the plan analyzed.

The paper's §IV dataflow/energy model only means anything if the execution
configuration it analyzes matches what actually dispatches. This module
walks the *static* surfaces — ``plan_sites``/``execution_plan()``,
``describe_execution(mesh)``, the serving-cache constructors — for every
registered config x policy preset, without running a single kernel, and
reports:

* overrides naming sites no model registers (``audit.plan.overrides``) —
  errors; before the site-table registry a typo silently fell back;
* %8 packing demotions not marked :attr:`SiteDecision.expected`
  (``audit.plan.packing``) — errors: an unplanned demotion means the
  measured energy/latency silently diverges from the analyzed dataflow;
* ``tokenizer.bn``/``tokenizer.lif`` rows that fused conv impls make
  never-dispatched but that lack the plan annotation
  (``audit.plan.annotation``) — errors;
* fused-epilogue sites whose train-arm VMEM estimate exceeds
  ``TRAIN_ARM_VMEM_BUDGET`` on the compiling backend
  (``audit.plan.vmem``) — warnings: the runtime guard demotes these to the
  pipeline arm gracefully, but the audit surfaces *where* the single-launch
  plan will not survive contact with the hardware;
* serving-cache slot-axis inconsistencies between ``init_cache``,
  ``cache_batch_axes`` and ``reset_cache_slots`` (``audit.serving.cache``)
  — errors, checked shape-only via ``jax.eval_shape`` (no allocation);
* ``describe_execution(mesh)`` failures on a small set of mesh shapes
  (``audit.mesh.describe``) — errors;
* tuned-block table entries (``audit.tune.table``) whose keys are
  malformed, name sites no model registers, carry ops/impls the kernel
  registry does not know (or that have no block knobs), or whose packed
  shape violates the %8 packing contract — errors: a stale or mistyped
  entry would silently never be consulted (or worse, consulted with
  blocks tuned for a different kernel).

Everything returns :class:`repro.analysis.report.Finding` rows; the CLI
(``python -m repro.analysis --audit``) turns errors into a non-zero exit.
"""
from __future__ import annotations

from typing import Iterable, Mapping, Sequence

from repro.analysis.report import Finding, error, info, warning
from repro.core.spikingformer import fused_site_geometries

__all__ = ["audit_breaker", "audit_mesh_plans", "audit_serving_caches",
           "audit_spikingformer_plans", "audit_tuned_table",
           "fused_site_geometries", "run_audit"]

#: Arch families whose decode path has no slot cache contract (the audio
#: encoder-decoder serves through a different entry point).
_SKIP_CACHE_FAMILIES = {"audio"}


# ---------------------------------------------------------------------------
# Plan audit: presets x policies
# ---------------------------------------------------------------------------

def audit_spikingformer_plans(presets: Sequence[str] | None = None,
                              policies: Mapping[str, object] | None = None,
                              *, batch: int = 1) -> list[Finding]:
    """Audit every preset x policy plan (see module docstring)."""
    from repro.configs.spikingformer import (SPIKINGFORMER_PRESETS,
                                             get_spikingformer_config)
    from repro.core.policy import NAMED_POLICIES, FUSED_EPILOGUE_IMPLS
    from repro.core.spikingformer import (FUSED_CONV_IMPLS,
                                          SINGLE_LAUNCH_CONV_IMPLS)
    from repro.kernels.neuron_layer import (TRAIN_ARM_VMEM_BUDGET,
                                            train_arm_vmem_bytes)

    presets = list(presets if presets is not None
                   else sorted(SPIKINGFORMER_PRESETS))
    policies = dict(policies if policies is not None else NAMED_POLICIES)
    findings: list[Finding] = []
    for preset in presets:
        for polname, pol in policies.items():
            where = f"{preset}@{polname}"
            try:
                cfg = get_spikingformer_config(preset, policy=pol)
                rows = cfg.execution_plan()
            except (ValueError, KeyError) as e:
                findings.append(error("audit.plan.overrides", where, str(e)))
                continue
            by_site = {r.site: r for r in rows}

            for r in rows:
                if "% 8" in r.note and not r.expected:
                    findings.append(error(
                        "audit.plan.packing", f"{where}/{r.site}",
                        f"unplanned packing demotion ({r.note}): the "
                        f"analyzed dataflow assumes the packed arm — mark "
                        f"the decision expected in the model's "
                        f"execution_plan() or fix the shape"))

            # Never-dispatched sites must say so in the plan: if every conv
            # stage runs a fused impl, the standalone bn (and, under the
            # megakernel, lif) site never dispatches.
            conv = [r for r in rows if r.op == "conv"]
            for site, impls, what in (
                    ("tokenizer.bn", FUSED_CONV_IMPLS, "BN fold"),
                    ("tokenizer.lif", SINGLE_LAUNCH_CONV_IMPLS,
                     "SOMA absorption")):
                row = by_site.get(site)
                if row is not None and conv and \
                        all(r.effective in impls for r in conv) and \
                        not row.note:
                    findings.append(error(
                        "audit.plan.annotation", f"{where}/{site}",
                        f"site never dispatches under the fused conv "
                        f"impls but its plan row carries no {what} "
                        f"annotation — the reported plan claims an impl "
                        f"that never runs"))

            if cfg.policy.backend == "pallas":
                geoms = fused_site_geometries(cfg, batch)
                for r in rows:
                    if r.effective not in FUSED_EPILOGUE_IMPLS:
                        continue
                    t, m, c, k = geoms[r.site]
                    need = train_arm_vmem_bytes(t, m, c, k, r.packed)
                    if need > TRAIN_ARM_VMEM_BUDGET:
                        findings.append(warning(
                            "audit.plan.vmem",
                            f"{where}/{r.site}",
                            f"train-arm VMEM estimate {need / 2**20:.1f}"
                            f"MiB exceeds the "
                            f"{TRAIN_ARM_VMEM_BUDGET / 2**20:.1f}MiB "
                            f"budget at batch={batch} — the runtime "
                            f"guard will demote this site to the "
                            f"pipeline arm on compiling backends"))
            findings.append(info(
                "audit.plan", where,
                f"{len(rows)} sites resolved, "
                f"{sum(1 for r in rows if r.note)} annotated"))
    return findings


# ---------------------------------------------------------------------------
# Serving-cache audit: slot-axis consistency, shape-only
# ---------------------------------------------------------------------------

def audit_serving_caches(arch_names: Sequence[str] | None = None, *,
                         slots: int = 4, max_seq: int = 32) -> list[Finding]:
    """Check ``init_cache``/``cache_batch_axes``/``reset_cache_slots``
    agree on every leaf's slot axis, for every (reduced) registered arch —
    with and without the spiking-LM LIF state. ``jax.eval_shape`` only:
    nothing is allocated, so the full registry audits in milliseconds."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import ASSIGNED, get_config, reduced
    from repro.core.lif import LIFConfig
    from repro.models.lm import (cache_batch_axes, init_cache,
                                 reset_cache_slots)

    findings: list[Finding] = []
    names = list(arch_names if arch_names is not None else ASSIGNED)
    for name in names:
        base = reduced(get_config(name))
        if base.family in _SKIP_CACHE_FAMILIES:
            findings.append(info("audit.serving.cache", name,
                                 f"family {base.family!r} has no decode "
                                 f"slot cache; skipped"))
            continue
        for cfg, tag in ((base, name),
                         (base.replace(lif=LIFConfig()), f"{name}+lif")):
            try:
                cache = jax.eval_shape(
                    lambda c=cfg: init_cache(c, slots, max_seq,
                                             jnp.float32))
                axes = cache_batch_axes(cfg, cache)
                if jax.tree.structure(axes) != jax.tree.structure(cache):
                    findings.append(error(
                        "audit.serving.cache", tag,
                        "cache_batch_axes returns a different pytree "
                        "structure than init_cache"))
                    continue
                bad = [
                    (path, leaf.shape, ax)
                    for (path, leaf), (_, ax)
                    in zip(jax.tree_util.tree_flatten_with_path(cache)[0],
                           jax.tree_util.tree_flatten_with_path(axes)[0])
                    if not (0 <= ax < leaf.ndim
                            and leaf.shape[ax] == slots)]
                for path, shape, ax in bad:
                    findings.append(error(
                        "audit.serving.cache",
                        f"{tag}{jax.tree_util.keystr(path)}",
                        f"declared slot axis {ax} of shape {shape} does "
                        f"not hold {slots} slots — reset_cache_slots "
                        f"would zero the wrong dimension"))
                mask = jax.ShapeDtypeStruct((slots,), jnp.bool_)
                # cfg rides in the closure: eval_shape would trace it as a
                # pytree leaf if passed positionally.
                after = jax.eval_shape(
                    lambda ca, m, c=cfg: reset_cache_slots(ca, m, c),
                    cache, mask)
                same = jax.tree.structure(after) == \
                    jax.tree.structure(cache) and all(
                    a.shape == b.shape and a.dtype == b.dtype
                    for a, b in zip(jax.tree.leaves(after),
                                    jax.tree.leaves(cache)))
                if not same:
                    findings.append(error(
                        "audit.serving.cache", tag,
                        "reset_cache_slots does not preserve the cache's "
                        "structure/shapes/dtypes"))
                if not bad and same:
                    findings.append(info(
                        "audit.serving.cache", tag,
                        f"{len(jax.tree.leaves(cache))} leaves consistent"))
            except Exception as e:   # noqa: BLE001 - report, don't crash
                findings.append(error("audit.serving.cache", tag,
                                      f"cache construction failed: {e}"))
    return findings


# ---------------------------------------------------------------------------
# Tuned-block table audit: key validation against the kernel registry
# ---------------------------------------------------------------------------

def audit_tuned_table(path: str | None = None) -> list[Finding]:
    """Validate a tuned-block table (``repro.tune.table``) key by key.

    ``path=None`` audits the active table (``$REPRO_TUNED_BLOCKS`` or the
    repo default); no active table is an info, not an error — tuned blocks
    are an optional acceleration layer. Every entry must name a site the
    site-key registry knows, a registered ``(op, impl)`` that actually has
    block knobs (``repro.tune.workloads.TUNABLE_IMPLS``), a well-formed
    shape, a valid arm, and — when marked packed — a contraction dim
    honouring the %8 packing contract. Version mismatches are errors here
    (dispatch merely ignores such tables, but an audited artifact claiming
    to be a tuned table must actually load).
    """
    import json as _json
    import pathlib

    from repro.core.policy import OPS, available_impls, known_site_keys
    from repro.tune.table import (ARMS, TABLE_VERSION, parse_key,
                                  table_path)
    from repro.tune.workloads import TUNABLE_IMPLS

    findings: list[Finding] = []
    p = pathlib.Path(path) if path is not None else table_path()
    if p is None:
        return [info("audit.tune.table", "-",
                     "no tuned-block table active; kernel defaults apply")]
    try:
        raw = _json.loads(p.read_text())
    except (OSError, _json.JSONDecodeError) as e:
        return [error("audit.tune.table", str(p), f"unreadable table: {e}")]
    if raw.get("version") != TABLE_VERSION:
        return [error("audit.tune.table", str(p),
                      f"version {raw.get('version')!r} unsupported "
                      f"(expected {TABLE_VERSION}); dispatch would ignore "
                      f"this table entirely")]
    sites = known_site_keys()
    bad = 0
    for key, entry in sorted(raw.get("entries", {}).items()):
        where = f"{p.name}/{key}"
        try:
            _, site, op, impl, shape, packed = parse_key(key)
        except ValueError as e:
            findings.append(error("audit.tune.table", where, str(e)))
            bad += 1
            continue
        problems = []
        if site not in sites:
            problems.append(f"unknown site {site!r} (stale key?)")
        if op not in OPS:
            problems.append(f"unknown op {op!r}")
        elif impl not in available_impls(op):
            problems.append(f"impl {impl!r} not registered for op {op!r}")
        elif (op, impl) not in TUNABLE_IMPLS:
            problems.append(f"({op}, {impl}) has no block knobs — entry "
                            f"can never be consulted")
        if not shape or any(d <= 0 for d in shape):
            problems.append(f"malformed shape {shape}")
        elif packed and len(shape) >= 2 and shape[-2] % 8 != 0:
            problems.append(f"packed entry but contraction dim "
                            f"{shape[-2]} % 8 != 0")
        arm = entry.get("arm")
        if arm is not None and arm not in ARMS:
            problems.append(f"unknown arm {arm!r}")
        for name in ("block_m", "block_k", "block_c"):
            v = entry.get(name)
            if v is not None and (not isinstance(v, int) or v <= 0):
                problems.append(f"{name}={v!r} is not a positive int")
        for msg in problems:
            findings.append(error("audit.tune.table", where, msg))
        bad += bool(problems)
    n = len(raw.get("entries", {}))
    findings.append(info("audit.tune.table", str(p),
                         f"{n} entries, {bad} invalid"))
    return findings


# ---------------------------------------------------------------------------
# Mesh audit: describe_execution on a small set of mesh shapes
# ---------------------------------------------------------------------------

def audit_mesh_plans(presets: Sequence[str] | None = None,
                     mesh_shapes: Iterable[tuple[int, int]] = ((1, 1),
                                                               (2, 4)),
                     ) -> list[Finding]:
    """``describe_execution(mesh)`` must render (dispatch + sharding
    tables) for every preset on every mesh shape that fits the local
    device count — a spec/shape mismatch raises deep inside jax, so a
    clean render is a real invariant."""
    import jax

    from repro.configs.spikingformer import (SPIKINGFORMER_PRESETS,
                                             get_spikingformer_config)
    from repro.launch.mesh import make_test_mesh

    presets = list(presets if presets is not None
                   else sorted(SPIKINGFORMER_PRESETS))
    n_dev = len(jax.devices())
    findings: list[Finding] = []
    for data, model in mesh_shapes:
        if data * model > n_dev:
            findings.append(info(
                "audit.mesh.describe", f"mesh=({data},{model})",
                f"skipped: needs {data * model} devices, have {n_dev}"))
            continue
        mesh = make_test_mesh(data, model)
        for preset in presets:
            where = f"{preset}/mesh=({data},{model})"
            try:
                out = get_spikingformer_config(preset) \
                    .describe_execution(mesh)
                if "Sharding plan" not in out or "site,op" not in out:
                    findings.append(error(
                        "audit.mesh.describe", where,
                        "describe_execution(mesh) rendered without the "
                        "dispatch or sharding table"))
                else:
                    findings.append(info("audit.mesh.describe", where,
                                         f"{len(out.splitlines())} lines"))
            except Exception as e:   # noqa: BLE001 - report, don't crash
                findings.append(error("audit.mesh.describe", where,
                                      f"describe_execution failed: {e}"))
    return findings


def audit_breaker() -> list[Finding]:
    """Report every circuit-breaker trip in this process
    (``audit.breaker``) — warnings: a tripped site means a registered impl
    raised at dispatch and the run silently-but-loggedly served the jnp
    reference there. Empty (and a fresh CI process always is) when no site
    tripped; in-process audits after a training/serving run surface the
    demotions here next to the plan findings."""
    from repro.core.policy import breaker_trips

    return [warning("audit.breaker", site,
                    f"impl {t.impl!r} (op {t.op}) tripped -> {t.fallback!r}: "
                    f"{t.error}")
            for site, t in sorted(breaker_trips().items())]


def run_audit(*, batch: int = 1,
              presets: Sequence[str] | None = None,
              policies: Mapping[str, object] | None = None,
              arch_names: Sequence[str] | None = None) -> list[Finding]:
    """The full static audit (plans + serving caches + tuned table +
    mesh renders + any in-process circuit-breaker trips)."""
    return (audit_spikingformer_plans(presets, policies, batch=batch)
            + audit_serving_caches(arch_names)
            + audit_tuned_table()
            + audit_mesh_plans(presets)
            + audit_breaker())
