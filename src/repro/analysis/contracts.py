"""Kernel-contract verifier: abstract BlockSpec/grid, custom-VJP and
reference-parity checking — without executing a single Pallas kernel.

The plan auditor (``audit.py``) checks *which* kernel runs where; this
module checks the kernels themselves. Every Pallas entry point declares a
:class:`~repro.kernels.contract.KernelContract` (builder + jnp oracle +
the ``(op, impl)`` pairs it serves); the verifier walks the full preset x
policy x site matrix (geometries from
:func:`repro.tune.workloads.kernel_shape_cases`), traces each declared
kernel with ``jax.eval_shape`` under a ``pallas_call`` interceptor, and
verifies four contract families on the recorded launches:

* ``audit.kernel.block`` — block shapes legally tile the (padded) operand
  shapes, every ``index_map`` output stays in range over the entire grid,
  ``index_map`` arity matches the grid rank, declared grids cover the
  output, and TPU (8, 128) sublane/lane alignment holds (warning).
* ``audit.kernel.vjp`` — for every ``custom_vjp`` op in ``kernels/ops.py``
  (plus the ``fire`` surrogate), ``jax.eval_shape`` the fwd/bwd pair and
  assert the cotangent pytree matches the primal-input avals exactly —
  shape, dtype and structure — at fp32 *and* bf16 (silent fp32 upcasts,
  dropped carries), and that the op's own output avals match its fwd's.
* ``audit.kernel.parity`` — each kernel's output avals must match its
  ``ref.py`` jnp oracle's at every planned site geometry.
* ``audit.kernel.vmem`` — per-launch VMEM accounting (declared scratch +
  one block tile per operand/output) against the train-arm budget, for
  every impl arm rather than just the fused-epilogue sites.

Plus ``audit.kernel.coverage`` (every registered non-``jnp`` impl is
served by at least one declaration, and no declaration serves a phantom
pair) and ``audit.trace.registry`` — the registry-wide retrace sanitizer:
policy-equivalent spellings of the same config must compare and *hash*
equal, because the jitted train/serve steps take the config as a static
argument and an unstable hash means one trace per spelling.

Everything here is ``jax.eval_shape`` under ``jax.disable_jit()`` — the
interceptor replaces ``pallas_call`` with a recorder that returns zeros of
the declared ``out_shape``, and ``disable_jit`` keeps the fake trace out
of every jit cache.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
import itertools
import math
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp

from repro.analysis.report import Finding, error, info, warning

#: Full-grid index_map enumeration cap; beyond it only the corner points
#: of each grid axis are checked (monotone index maps — all of ours — hit
#: their extremes there).
_GRID_ENUM_CAP = 65536

#: Geometry for the dtype-swept custom-VJP checks (kernel-legal: the
#: contraction/feature dims satisfy the %8 packing contract).
_VJP_GEOM = {"t": 2, "m": 16, "c": 16, "k": 16, "g": 2}

_VJP_DTYPES = ("float32", "bfloat16")


def _is_sds(x) -> bool:
    return isinstance(x, jax.ShapeDtypeStruct)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# pallas_call interception: record every launch, return abstract zeros
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PallasCallRecord:
    """One intercepted ``pallas_call``: everything the static checks need."""

    kernel: str
    grid: tuple[int, ...]
    in_specs: tuple
    out_specs: tuple
    out_shape: tuple            # ShapeDtypeStruct leaves, same order as specs
    scratch_shapes: tuple
    operands: tuple             # ((shape, dtype), ...) of the call args


def _kernel_name(kernel) -> str:
    fn = getattr(kernel, "func", kernel)
    return getattr(fn, "__name__", repr(kernel))


def _as_list(x) -> list:
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


@contextlib.contextmanager
def intercept_pallas_calls(records: list[PallasCallRecord]):
    """Swap ``pallas.pallas_call`` for a recorder that never runs a kernel
    and yields zeros of the declared ``out_shape``. Use together with
    ``jax.disable_jit()`` so no jit cache ever sees the fake trace."""
    from jax.experimental import pallas as pl_mod

    real = pl_mod.pallas_call

    def fake(kernel, out_shape=None, *, grid=None, in_specs=None,
             out_specs=None, scratch_shapes=None, interpret=None, **kw):
        del interpret, kw

        def runner(*operands):
            g = (grid,) if isinstance(grid, int) else tuple(grid or ())
            records.append(PallasCallRecord(
                kernel=_kernel_name(kernel), grid=g,
                in_specs=tuple(_as_list(in_specs)),
                out_specs=tuple(_as_list(out_specs)),
                out_shape=tuple(jax.tree.leaves(out_shape, is_leaf=_is_sds)),
                scratch_shapes=tuple(_as_list(scratch_shapes)),
                operands=tuple((tuple(o.shape), jnp.dtype(o.dtype))
                               for o in operands)))
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                out_shape, is_leaf=_is_sds)

        return runner

    pl_mod.pallas_call = fake
    try:
        yield
    finally:
        pl_mod.pallas_call = real


def abstract_eval(fn: Callable, args: tuple, kwargs: dict | None = None
                  ) -> tuple[Any, list[PallasCallRecord]]:
    """``jax.eval_shape`` ``fn`` with every ``pallas_call`` intercepted;
    returns ``(output avals, launch records)``. Zero kernels execute."""
    records: list[PallasCallRecord] = []
    f = functools.partial(fn, **(kwargs or {}))
    with intercept_pallas_calls(records), jax.disable_jit():
        out = jax.eval_shape(f, *args)
    return out, records


# ---------------------------------------------------------------------------
# audit.kernel.block — BlockSpec/grid legality per recorded launch
# ---------------------------------------------------------------------------

def _index_map_arity(index_map) -> int | None:
    try:
        params = inspect.signature(index_map).parameters.values()
        return sum(1 for p in params
                   if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD))
    except (TypeError, ValueError):
        return None


def _grid_points(grid: tuple[int, ...]) -> tuple[Iterator, bool]:
    """(iterator over grid points, exhaustive?) — full enumeration up to
    the cap, axis-corner product beyond it."""
    total = math.prod(grid) if grid else 0
    if total <= _GRID_ENUM_CAP:
        return itertools.product(*(range(g) for g in grid)), True
    corners = [sorted({0, g - 1}) for g in grid]
    return itertools.product(*corners), False


def _check_spec(rec: PallasCallRecord, spec, shape: tuple[int, ...],
                dtype, role: str, where: str) -> list[Finding]:
    """Validate one BlockSpec against the operand/output it maps."""
    out: list[Finding] = []
    label = f"{where}:{rec.kernel}/{role}"
    block = tuple(getattr(spec, "block_shape", ()) or ())
    if len(block) != len(shape):
        out.append(error("audit.kernel.block", label,
                         f"block rank {len(block)} != operand rank "
                         f"{len(shape)} (block {block}, operand {shape})"))
        return out
    for d, (b, s) in enumerate(zip(block, shape)):
        if not isinstance(b, int) or b <= 0:
            out.append(error("audit.kernel.block", label,
                             f"non-positive block dim {b!r} at axis {d}"))
            return out
        if b > s:
            out.append(error("audit.kernel.block", label,
                             f"block dim {b} exceeds operand dim {s} at "
                             f"axis {d}"))
    # TPU sublane/lane alignment ((8, 128) fp32 min tile): a block dim must
    # be tile-aligned or cover the whole axis — the packed uint8 operand
    # too (its contraction tiles are whole 128-byte pack groups, or all of
    # C; see ``spike_matmul.contraction_block``).
    if len(block) >= 2:
        b_last, s_last = block[-1], shape[-1]
        if b_last % 128 != 0 and b_last != s_last:
            out.append(warning(
                "audit.kernel.block", label,
                f"last block dim {b_last} neither a multiple of 128 nor "
                f"the full axis {s_last} — padded lanes on TPU"))
    if len(block) >= 2:
        b_sub, s_sub = block[-2], shape[-2]
        if b_sub % 8 != 0 and b_sub != s_sub:
            out.append(warning(
                "audit.kernel.block", label,
                f"second-to-last block dim {b_sub} neither a multiple of 8 "
                f"nor the full axis {s_sub} — padded sublanes on TPU"))
    index_map = getattr(spec, "index_map", None)
    if index_map is None:
        return out
    arity = _index_map_arity(index_map)
    if arity is not None and arity != len(rec.grid):
        out.append(error("audit.kernel.block", label,
                         f"index_map arity {arity} != grid rank "
                         f"{len(rec.grid)} (grid {rec.grid})"))
        return out
    nblocks = tuple(_cdiv(s, b) for s, b in zip(shape, block))
    points, exhaustive = _grid_points(rec.grid)
    seen: set[tuple[int, ...]] = set()
    for pt in points:
        try:
            idx = index_map(*pt)
        except Exception as e:
            out.append(error("audit.kernel.block", label,
                             f"index_map raised at grid point {pt}: {e!r}"))
            return out
        idx = tuple(idx) if isinstance(idx, (tuple, list)) else (idx,)
        if len(idx) != len(shape):
            out.append(error("audit.kernel.block", label,
                             f"index_map returned rank {len(idx)} for "
                             f"operand rank {len(shape)} at {pt}"))
            return out
        for d, (i, nb) in enumerate(zip(idx, nblocks)):
            if not (0 <= int(i) < nb):
                out.append(error(
                    "audit.kernel.block", label,
                    f"index_map output {idx} out of range at grid point "
                    f"{pt}: axis {d} has {nb} block(s) of {block[d]} over "
                    f"dim {shape[d]}"))
                return out
        seen.add(tuple(int(i) for i in idx))
    if role.startswith("out") and exhaustive:
        expected = math.prod(nblocks)
        if len(seen) != expected:
            out.append(error(
                "audit.kernel.block", label,
                f"grid {rec.grid} covers {len(seen)}/{expected} output "
                f"blocks — declared grid does not cover the output"))
    return out


def check_block_contracts(rec: PallasCallRecord, where: str
                          ) -> list[Finding]:
    out: list[Finding] = []
    label = f"{where}:{rec.kernel}"
    if rec.in_specs and len(rec.in_specs) != len(rec.operands):
        out.append(error("audit.kernel.block", label,
                         f"{len(rec.in_specs)} in_specs for "
                         f"{len(rec.operands)} operands"))
        return out
    if rec.out_specs and len(rec.out_specs) != len(rec.out_shape):
        out.append(error("audit.kernel.block", label,
                         f"{len(rec.out_specs)} out_specs for "
                         f"{len(rec.out_shape)} outputs"))
        return out
    for i, (spec, (shape, dtype)) in enumerate(
            zip(rec.in_specs, rec.operands)):
        out += _check_spec(rec, spec, shape, dtype, f"in[{i}]", where)
    for i, (spec, sds) in enumerate(zip(rec.out_specs, rec.out_shape)):
        out += _check_spec(rec, spec, tuple(sds.shape), sds.dtype,
                           f"out[{i}]", where)
    return out


# ---------------------------------------------------------------------------
# audit.kernel.vmem — per-launch scratch + block-tile accounting
# ---------------------------------------------------------------------------

def _tile_bytes(spec, dtype) -> int:
    block = tuple(getattr(spec, "block_shape", ()) or ())
    if not block:
        return 0
    return math.prod(block) * jnp.dtype(dtype).itemsize


def launch_vmem_bytes(rec: PallasCallRecord) -> int:
    """Estimated VMEM residency of one launch: declared scratch buffers
    plus one block tile per operand and output."""
    total = 0
    for s in rec.scratch_shapes:
        shape = tuple(getattr(s, "shape", ()) or ())
        dtype = getattr(s, "dtype", jnp.float32)
        total += math.prod(shape) * jnp.dtype(dtype).itemsize
    for spec, (shape, dtype) in zip(rec.in_specs, rec.operands):
        total += _tile_bytes(spec, dtype)
    for spec, sds in zip(rec.out_specs, rec.out_shape):
        total += _tile_bytes(spec, sds.dtype)
    return total


def check_vmem_contract(rec: PallasCallRecord, where: str,
                        budget: int) -> list[Finding]:
    est = launch_vmem_bytes(rec)
    if est <= budget:
        return []
    return [warning(
        "audit.kernel.vmem", f"{where}:{rec.kernel}",
        f"estimated VMEM residency {est >> 20} MiB (scratch + block tiles) "
        f"> budget {budget >> 20} MiB — the runtime guard must demote this "
        f"arm on a compiling backend")]


# ---------------------------------------------------------------------------
# audit.kernel.parity — kernel avals vs the ref.py oracle avals
# ---------------------------------------------------------------------------

def _aval_list(tree) -> list[tuple[tuple[int, ...], Any]]:
    return [(tuple(l.shape), jnp.dtype(l.dtype))
            for l in jax.tree.leaves(tree, is_leaf=_is_sds)]


def _aval_str(avals) -> str:
    return ", ".join(f"{dt.name}{list(sh)}" for sh, dt in avals)


def check_parity_contract(decl, args: tuple, ref_kwargs: dict, out,
                          where: str) -> list[Finding]:
    try:
        with jax.disable_jit():
            ref_out = jax.eval_shape(
                functools.partial(decl.ref, **ref_kwargs), *args)
    except Exception as e:
        return [error("audit.kernel.parity", where,
                      f"reference {decl.ref.__name__} failed to trace: "
                      f"{e!r}")]
    got, want = _aval_list(out), _aval_list(ref_out)
    if got != want:
        return [error(
            "audit.kernel.parity", where,
            f"kernel avals [{_aval_str(got)}] != reference "
            f"{decl.ref.__name__} avals [{_aval_str(want)}]")]
    return []


# ---------------------------------------------------------------------------
# The preset x policy x site matrix walk
# ---------------------------------------------------------------------------

def _contract_index():
    """(op, impl) -> [KernelContract], plus the declaration dict."""
    from repro.kernels.contract import kernel_contracts

    decls = kernel_contracts()
    by_pair: dict[tuple[str, str], list] = {}
    for decl in decls.values():
        for pair in decl.serves:
            by_pair.setdefault(pair, []).append(decl)
    return decls, by_pair


def audit_kernel_coverage() -> list[Finding]:
    """Every registered non-exempt (op, impl) pair has a declaration, and
    every declaration serves only registered pairs."""
    from repro.core.policy import CONTRACT_EXEMPT_IMPLS, registered_kernels

    decls, by_pair = _contract_index()
    registered = set(registered_kernels())
    out: list[Finding] = []
    for op, impl in sorted(registered):
        if impl in CONTRACT_EXEMPT_IMPLS:
            continue
        if (op, impl) not in by_pair:
            out.append(error(
                "audit.kernel.coverage", f"{op}/{impl}",
                "registered implementation has no KernelContract "
                "declaration (repro.kernels.contract) — its BlockSpecs, "
                "VJP and reference parity are unverified"))
    for name, decl in sorted(decls.items()):
        for pair in decl.serves:
            if pair not in registered:
                out.append(error(
                    "audit.kernel.coverage", name,
                    f"declaration serves unregistered pair {pair!r}"))
    return out


def audit_kernel_matrix(*, batch: int = 1, presets=None, policies=None,
                        vmem_budget: int | None = None) -> list[Finding]:
    """Walk every preset x policy x planned site, feed each declared
    kernel its abstract geometry, and run the block/parity/vmem checks on
    the recorded launches. Deduplicates identical (kernel, geometry)
    pairs across the matrix."""
    from repro.configs.spikingformer import (SPIKINGFORMER_PRESETS,
                                             get_spikingformer_config)
    from repro.core.policy import CONTRACT_EXEMPT_IMPLS, NAMED_POLICIES
    from repro.kernels.contract import KernelCase, SkipCase
    from repro.kernels.neuron_layer import TRAIN_ARM_VMEM_BUDGET
    from repro.tune.workloads import kernel_shape_cases

    budget = TRAIN_ARM_VMEM_BUDGET if vmem_budget is None else vmem_budget
    _, by_pair = _contract_index()
    findings: list[Finding] = []
    seen: set[tuple] = set()
    checked = 0
    for preset in presets or sorted(SPIKINGFORMER_PRESETS):
        for polname, pol in (policies or NAMED_POLICIES).items():
            cfg = get_spikingformer_config(preset, policy=pol)
            for row in kernel_shape_cases(cfg, batch=batch):
                if row.impl in CONTRACT_EXEMPT_IMPLS:
                    continue
                case = KernelCase(t=row.t, m=row.m, c=row.c, k=row.k,
                                  packed=row.packed)
                where = f"{preset}@{polname}/{row.site}"
                for decl in by_pair.get((row.op, row.impl), ()):
                    key = (decl.name, case)
                    if key in seen:
                        continue
                    seen.add(key)
                    label = f"{where}[{decl.name}]"
                    try:
                        args, fn_kwargs, ref_kwargs = decl.build(case)
                    except SkipCase:
                        continue
                    except Exception as e:
                        findings.append(error(
                            "audit.kernel.block", label,
                            f"builder failed at {case.shape4}: {e!r}"))
                        continue
                    try:
                        out, records = abstract_eval(decl.fn, args,
                                                     fn_kwargs)
                    except Exception as e:
                        findings.append(error(
                            "audit.kernel.block", label,
                            f"abstract trace failed at {case.shape4}: "
                            f"{e!r}"))
                        continue
                    checked += 1
                    if not records:
                        findings.append(warning(
                            "audit.kernel.block", label,
                            "declared kernel traced no pallas_call at "
                            f"{case.shape4}"))
                    for rec in records:
                        findings += check_block_contracts(rec, label)
                        findings += check_vmem_contract(rec, label, budget)
                    if decl.ref is not None:
                        findings += check_parity_contract(
                            decl, args, ref_kwargs, out, label)
    findings.append(info(
        "audit.kernel.block", "matrix",
        f"{checked} distinct (kernel, geometry) contracts verified "
        "abstractly — zero Pallas kernels executed"))
    return findings


# ---------------------------------------------------------------------------
# audit.kernel.vjp — custom_vjp cotangent/primal aval agreement
# ---------------------------------------------------------------------------

def _vjp_cases(dtype: str):
    """The 9 custom_vjp ops: (name, op, full arg list) where each arg is
    ('aval', ShapeDtypeStruct) or ('static', value) following the op's
    ``nondiff_argnums``."""
    from repro.core.lif import fire
    from repro.kernels import ops

    t, m, c, k, g = (_VJP_GEOM[x] for x in "tmckg")
    f = jax.ShapeDtypeStruct

    def a(*shape):
        return ("aval", f(shape, dtype))

    def s(v):
        return ("static", v)

    return [
        ("lif_soma_op", ops.lif_soma_op,
         [a(t, m, k), s(0.5), s(1.0), s(0.0), s(2.0), s(1.0), s(None)]),
        ("lif_soma_carry_op", ops.lif_soma_carry_op,
         [a(t, m, k), a(m, k), a(m, k),
          s(0.5), s(1.0), s(0.0), s(2.0), s(1.0), s(None)]),
        ("bn_train_op", ops.bn_train_op,
         [a(m, k), a(k), a(k), s(1e-5), s(None)]),
        ("spike_matmul_train_op", ops.spike_matmul_train_op,
         [a(m, c), a(c, k), s(None), s(None)]),
        ("spike_bmm_train_op", ops.spike_bmm_train_op,
         [a(g, m, c), a(g, c, k), s(None), s(None)]),
        ("spike_patch_mm_train_op", ops.spike_patch_mm_train_op,
         [a(t, m, c), a(c, k), s(None), s(None)]),
        ("neuron_layer_train_op", ops.neuron_layer_train_op,
         [a(t, m, c), a(c, k), a(k), a(k),
          s(0.5), s(1.0), s(0.0), s(2.0), s(1.0), s(1e-5), s(False),
          s(None), s(None)]),
        ("neuron_layer_eval_op", ops.neuron_layer_eval_op,
         [a(t, m, c), a(c, k), ("aval", f((k,), jnp.float32)),
          s(0.5), s(1.0), s(0.0), s(2.0), s(1.0), s(False), s(None),
          s(None)]),
        # The surrogate-gradient primitive itself: every arg is a primal
        # (no nondiff_argnums); the threshold cotangents are symbolic
        # zeros (None), which the check accepts.
        ("fire", fire, [a(m, k), s(1.0), s(0.0), s(2.0), s(1.0)]),
    ]


def _check_one_vjp(name: str, op, spec: list, dtype: str) -> list[Finding]:
    where = f"ops.{name}[{dtype}]"
    avals = tuple(v for kind, v in spec if kind == "aval")
    nondiff = tuple(getattr(op, "nondiff_argnums", ()) or ())
    statics = {i: v for i, (kind, v) in enumerate(spec) if kind == "static"}
    if not set(nondiff) <= set(statics):
        return [error("audit.kernel.vjp", where,
                      f"case table disagrees with nondiff_argnums "
                      f"{nondiff} (statics at {sorted(statics)})")]

    def merge(arrays):
        it = iter(arrays)
        return [statics[i] if i in statics else next(it)
                for i in range(len(spec))]

    fwd, bwd = getattr(op, "fwd", None), getattr(op, "bwd", None)
    if fwd is None or bwd is None:
        return [error("audit.kernel.vjp", where,
                      "op exposes no fwd/bwd pair")]
    out: list[Finding] = []
    records: list[PallasCallRecord] = []
    try:
        with intercept_pallas_calls(records), jax.disable_jit():
            primal_out, res = jax.eval_shape(
                lambda *arrs: fwd(*merge(arrs)), *avals)
            op_out = jax.eval_shape(lambda *arrs: op(*merge(arrs)), *avals)
    except Exception as e:
        return [error("audit.kernel.vjp", where,
                      f"fwd failed to trace abstractly: {e!r}")]
    if _aval_list(op_out) != _aval_list(primal_out):
        out.append(error(
            "audit.kernel.vjp", where,
            f"op output avals [{_aval_str(_aval_list(op_out))}] != fwd "
            f"primal-out avals [{_aval_str(_aval_list(primal_out))}] — "
            "fwd/fun disagree"))
    # bwd's positional prefix is exactly the nondiff args, in argnum order;
    # everything else in the spec is a primal owed a cotangent slot (for
    # ``fire`` the threshold floats are primals passed as python scalars —
    # their avals are weakly typed, so only their *slots* are checked).
    nd_values = tuple(statics[i] for i in sorted(nondiff))
    try:
        with intercept_pallas_calls(records), jax.disable_jit():
            cts = jax.eval_shape(lambda r, g: bwd(*nd_values, r, g),
                                 res, primal_out)
    except Exception as e:
        return out + [error("audit.kernel.vjp", where,
                            f"bwd failed to trace abstractly: {e!r}")]
    if not isinstance(cts, (tuple, list)):
        cts = (cts,)
    primal_avals = [v if kind == "aval" else None
                    for i, (kind, v) in enumerate(spec) if i not in nondiff]
    if len(cts) != len(primal_avals):
        out.append(error(
            "audit.kernel.vjp", where,
            f"bwd returned {len(cts)} cotangent(s) for "
            f"{len(primal_avals)} primal(s) — structure mismatch"))
        return out
    for i, (ct, primal) in enumerate(zip(cts, primal_avals)):
        if ct is None:
            continue  # symbolic-zero cotangent: always structurally valid
        if primal is None:
            continue  # python-scalar primal (weakly typed): skip
        got, want = _aval_list(ct), _aval_list(primal)
        if got != want:
            out.append(error(
                "audit.kernel.vjp", where,
                f"cotangent {i} avals [{_aval_str(got)}] != primal avals "
                f"[{_aval_str(want)}] — a dtype drift here is a silent "
                "fp32 upcast in the update"))
    return out


def audit_kernel_vjps() -> list[Finding]:
    """Abstractly check every custom_vjp fwd/bwd pair at fp32 and bf16."""
    findings: list[Finding] = []
    n = 0
    for dtype in _VJP_DTYPES:
        for name, op, spec in _vjp_cases(dtype):
            findings += _check_one_vjp(name, op, spec, dtype)
            n += 1
    findings.append(info(
        "audit.kernel.vjp", "ops",
        f"{n} custom_vjp fwd/bwd pairs eval_shape-checked across "
        f"{len(_VJP_DTYPES)} dtypes"))
    return findings


# ---------------------------------------------------------------------------
# audit.trace.registry — config factories must hash stably across
# policy-equivalent spellings (the jitted step's static arg)
# ---------------------------------------------------------------------------

def audit_registry_retrace(presets=None, policies=None) -> list[Finding]:
    """Every config-registry factory's jitted step traces exactly once
    across policy-equivalent spellings: ``name@policy`` suffix vs
    ``policy=`` kwarg with a freshly-constructed equal policy must produce
    configs that compare *and hash* equal — the train/serve steps take the
    config as a static jit argument, so an unstable hash is one silent
    retrace per spelling."""
    from repro.configs.registry import get_config, list_configs, reduced
    from repro.configs.spikingformer import (SPIKINGFORMER_PRESETS,
                                             get_spikingformer_config)
    from repro.core.policy import NAMED_POLICIES, ExecutionPolicy

    findings: list[Finding] = []
    for preset in presets or sorted(SPIKINGFORMER_PRESETS):
        for polname, pol in (policies or NAMED_POLICIES).items():
            where = f"spikingformer/{preset}@{polname}"
            # Spelling B rebuilds the policy from its parts (a Mapping
            # overrides value) — canonicalization must make it identical.
            pol_b = ExecutionPolicy(backend=pol.backend,
                                    interpret=pol.interpret,
                                    overrides=dict(pol.overrides))
            try:
                if polname in NAMED_POLICIES and policies is None:
                    cfg_a = get_spikingformer_config(f"{preset}@{polname}")
                else:
                    cfg_a = get_spikingformer_config(preset, policy=pol)
                cfg_b = get_spikingformer_config(preset, policy=pol_b)
            except Exception as e:
                findings.append(error("audit.trace.registry", where,
                                      f"factory raised: {e!r}"))
                continue
            try:
                ha, hb = hash(cfg_a), hash(cfg_b)
            except TypeError as e:
                findings.append(error(
                    "audit.trace.registry", where,
                    f"config not hashable ({e}) — it cannot be a static "
                    "jit argument at all"))
                continue
            if cfg_a != cfg_b:
                findings.append(error(
                    "audit.trace.registry", where,
                    "policy-equivalent spellings built unequal configs — "
                    "the jitted step retraces per spelling"))
            elif ha != hb:
                findings.append(error(
                    "audit.trace.registry", where,
                    "equal configs hash unequal — jit's static-argument "
                    "cache misses and silently retraces"))
    for name in list_configs():
        where = f"registry/{name}"
        try:
            cfg_a, cfg_b = get_config(name), get_config(name)
            ra, rb = reduced(cfg_a), reduced(cfg_b)
            ok = (cfg_a == cfg_b and hash(cfg_a) == hash(cfg_b)
                  and ra == rb and hash(ra) == hash(rb))
        except Exception as e:
            findings.append(error("audit.trace.registry", where,
                                  f"factory/hash raised: {e!r}"))
            continue
        if not ok:
            findings.append(error(
                "audit.trace.registry", where,
                "repeated factory lookups disagree (eq/hash) — one jit "
                "trace per lookup"))
    return findings


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def run_contracts(*, batch: int = 1, presets=None, policies=None,
                  vmem_budget: int | None = None) -> list[Finding]:
    """All contract families; returns Finding rows for report.py."""
    findings = audit_kernel_coverage()
    findings += audit_kernel_matrix(batch=batch, presets=presets,
                                    policies=policies,
                                    vmem_budget=vmem_budget)
    findings += audit_kernel_vjps()
    findings += audit_registry_retrace(presets=presets, policies=policies)
    return findings
