"""Execution policy + per-site kernel registry for the Spikingformer stack.

PR 1 threaded a flat ``backend``/``spike_mm``/``interpret`` triple through
every config and ``*_apply`` kwarg list. That cannot express "packed spike
matmul at the MLP sites but dense at the tokenizer" or "route the attention
einsums through the packed kernel" — so this module replaces the triple with
two pieces:

* :class:`ExecutionPolicy` — a frozen, hashable value (safe as a static jit
  argument) holding a default ``backend``, the Pallas ``interpret`` override,
  and a canonical tuple of per-site implementation overrides, e.g.::

      ExecutionPolicy(backend="pallas",
                      overrides={"pssa.qkv": "pallas+spike_mm",
                                 "attn_qk": "pallas_packed",
                                 "tokenizer.bn": "jnp"})

* a **kernel registry** keyed ``(op, impl)``. Ops are the abstract sites the
  model dispatches through (``lif``, ``bn``, ``linear_bn``, ``attn_qk``,
  ``attn_av``, ``conv``); impls are named implementations registered with
  :func:`register_kernel`. ``lif_scan`` / ``bn_apply`` / ``linear_bn_apply``
  / ``pssa_apply`` resolve through :meth:`ExecutionPolicy.resolve` instead of
  branching on booleans, so third parties can register new implementations
  (see ``docs/EXECUTION.md``) and A/B them per site.

Resolution precedence for ``resolve(site, op)``:

1. an override keyed by the exact *site* name (``"pssa.qkv"``),
2. an override keyed by a dotted *group prefix* of the site
   (``"tokenizer.conv"`` covers every per-stage ``"tokenizer.conv.<i>"``
   site; nearest prefix wins),
3. an override keyed by the *op* name (``"linear_bn"``),
4. the backend's default implementation for the op.

Packing constraints (the bit-packed spike kernels need their contraction
dim to be a multiple of 8, and a spike-valued operand) are resolved
**once, at policy-validation time** via :func:`plan_sites` — which reports
the effective implementation per site — instead of silently falling back
per call.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import warnings
from typing import Any, Callable, Iterable, Mapping, Sequence

from repro.core.backend import BACKENDS, validate_backend

logger = logging.getLogger("repro.execution")

#: The abstract op kinds the model dispatches through (a *site* is a named
#: instance of one of these, e.g. site "pssa.qkv" has op "linear_bn").
#: "lif_state" is the state-carrying LIF used by streaming/serving and by
#: the temporally-tiled (``time_chunk``) training scan; it shares the lif
#: site names, so a per-site override covers both the single-shot and the
#: tiled path at that site.
OPS: tuple[str, ...] = ("lif", "lif_state", "bn", "linear_bn", "attn_qk",
                        "attn_av", "conv")

# Per-backend default implementation for each op. The attention einsums and
# the tokenizer conv stay on their dense/einsum defaults even under
# backend="pallas" (packed attention and the fused im2col tokenizer conv
# are opt-in via the "pallas-full" policy until TPU-soaked).
_DEFAULT_IMPL: dict[tuple[str, str], str] = {
    ("lif", "jnp"): "jnp", ("lif", "pallas"): "pallas",
    ("lif_state", "jnp"): "jnp", ("lif_state", "pallas"): "pallas",
    ("bn", "jnp"): "jnp", ("bn", "pallas"): "pallas",
    ("linear_bn", "jnp"): "jnp", ("linear_bn", "pallas"): "pallas",
    ("attn_qk", "jnp"): "jnp", ("attn_qk", "pallas"): "jnp",
    ("attn_av", "jnp"): "jnp", ("attn_av", "pallas"): "jnp",
    ("conv", "jnp"): "jnp", ("conv", "pallas"): "jnp",
}

#: impl -> fallback impl used when a site's packing constraint
#: (contraction dim % 8 == 0, spike-valued operand) cannot be met.
PACKED_IMPL_FALLBACK: dict[str, str] = {
    "pallas+spike_mm": "pallas",   # dense matmul + fused BN
    "pallas_packed": "jnp",        # plain einsum
}

#: (op, impl) -> fallback, consulted before the impl-keyed table. The
#: packed tokenizer conv demotes to the *dense im2col* arm of the fused
#: conv+BN+LIF pipeline (still one matmul + folded BN + SOMA epilogue),
#: not all the way to the jnp reference conv.
_PACKED_OP_FALLBACK: dict[tuple[str, str], str] = {
    ("conv", "pallas_packed"): "pallas",
}


def packed_fallback(op: str, impl: str) -> str | None:
    """The dense fallback for a packed implementation at ``op`` (``None``
    when ``impl`` has no packing constraint)."""
    return _PACKED_OP_FALLBACK.get((op, impl), PACKED_IMPL_FALLBACK.get(impl))


#: Implementations that run the single-launch neuron-layer megakernel
#: (matmul + BN + SOMA in one Pallas kernel). Packing constraints do NOT
#: demote these away — the megakernel has a dense arm, so a ragged or
#: float-operand site keeps the single launch and only loses the bit-packed
#: HBM traffic (annotated in the plan). What *does* demote them is the site
#: itself: a ``linear_bn`` site with no trailing LIF (the Z-projection and
#: SMLP-B sites feed residual adds, not an SN) has no SOMA to fuse.
FUSED_EPILOGUE_IMPLS: frozenset[str] = frozenset({"fused_epilogue"})

#: (op, impl) -> demotion target at sites that structurally cannot host the
#: fused epilogue (no trailing LIF). Every conv site IS a Conv->BN->LIF
#: stage, so only linear_bn sites appear here.
_FUSED_EPILOGUE_FALLBACK: dict[tuple[str, str], str] = {
    ("linear_bn", "fused_epilogue"): "pallas+spike_mm",
}


def fused_epilogue_fallback(op: str, impl: str) -> str | None:
    """The pipeline (multi-launch) fallback for a fused-epilogue impl at a
    site with no trailing LIF (``None`` when ``impl`` is not one)."""
    return _FUSED_EPILOGUE_FALLBACK.get((op, impl))


def default_impl(op: str, backend: str) -> str:
    try:
        return _DEFAULT_IMPL[(op, validate_backend(backend))]
    except KeyError:
        raise ValueError(f"unknown op {op!r}; expected one of {OPS}") from None


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """Hashable execution policy: default backend + per-site overrides.

    ``overrides`` accepts a mapping or an iterable of ``(key, impl)`` pairs
    (keys are site names or op names) and is canonicalized to a sorted tuple
    so equal policies compare and hash equal — policies are static jit
    arguments and must never retrace when logically unchanged.
    """

    backend: str = "jnp"
    interpret: bool | None = None
    overrides: tuple[tuple[str, str], ...] = ()
    #: Validate override keys against the registered site tables at
    #: construction (``strict=False`` is the forward-compat escape hatch
    #: for policies naming sites of models this process never imports).
    #: Excluded from eq/hash: strictness is a construction-time check, not
    #: an execution behavior, and must never force a retrace.
    strict: bool = dataclasses.field(default=True, compare=False)

    def __post_init__(self):
        validate_backend(self.backend)
        ov = self.overrides
        if isinstance(ov, Mapping):
            ov = ov.items()
        object.__setattr__(
            self, "overrides",
            tuple(sorted((str(k), str(v)) for k, v in ov)))
        if self.strict:
            _validate_override_keys(self.overrides)

    def resolve(self, site: str, op: str) -> str:
        """Implementation name for ``site`` (an instance of ``op``).

        Site keys resolve hierarchically: the exact name first, then each
        dotted group prefix (``"tokenizer.conv.2"`` falls back to
        ``"tokenizer.conv"``, then ``"tokenizer"``), then the op name, then
        the backend default — so one override can cover a whole site group
        (e.g. every per-stage tokenizer conv).
        """
        ov = dict(self.overrides)
        key = site
        while True:
            impl = ov.get(key)
            if impl is not None:
                return impl
            if "." not in key:
                break
            key = key.rsplit(".", 1)[0]
        impl = ov.get(op)
        if impl is None:
            impl = default_impl(op, self.backend)
        return impl

    def with_sites(self, sites: Mapping[str, str | None]) -> "ExecutionPolicy":
        """New policy with ``sites`` merged in (``None`` removes a key)."""
        ov = dict(self.overrides)
        for k, v in sites.items():
            if v is None:
                ov.pop(k, None)
            else:
                ov[k] = v
        return dataclasses.replace(self, overrides=tuple(ov.items()))

    def describe(self, site_specs: Sequence[tuple] | None = None, *,
                 rows: Sequence["SiteDecision"] | None = None) -> str:
        """Human-readable per-site dispatch table.

        Without arguments the table shows the op-level defaults plus any
        overrides; with ``site_specs`` (``(site, op, pack_dim[,
        spike_operand])`` tuples) it shows the *effective* implementation
        per model site, including packing fallbacks. Callers that already
        hold resolved (possibly post-processed) :class:`SiteDecision` rows
        — e.g. ``SpikingFormerConfig.execution_plan`` with its
        ``tokenizer.bn`` fold annotation — pass them via ``rows`` instead.
        """
        if rows is None:
            if site_specs is None:
                site_specs = [(op, op, None) for op in OPS]
            rows = plan_sites(self, site_specs, check_registry=False)
        header = f"# ExecutionPolicy backend={self.backend} " \
                 f"interpret={self.interpret}"
        lines = [header, "site,op,requested,effective,note"]
        for r in rows:
            lines.append(f"{r.site},{r.op},{r.requested},{r.effective},"
                         f"{r.note}")
        return "\n".join(lines)


@dataclasses.dataclass(frozen=True)
class SiteDecision:
    """One row of a resolved execution plan.

    ``expected`` marks a *structural* demotion the model shape dictates by
    design (e.g. the float-image first tokenizer stage cannot ride the
    spike-packed conv) — reported at INFO, unlike constraint violations
    (ragged pack dims), which stay warnings. ``packed`` says whether the
    effective impl consumes bit-packed spikes (a fused-epilogue megakernel
    on its dense arm does not).
    """

    site: str
    op: str
    requested: str
    effective: str
    note: str = ""
    expected: bool = False
    packed: bool = False


def plan_sites(policy: ExecutionPolicy,
               site_specs: Sequence[tuple],
               *, check_registry: bool = True) -> list[SiteDecision]:
    """Resolve every site once and report packing/fusion fallbacks.

    ``site_specs`` is a sequence of ``(site, op, pack_dim)``, ``(site, op,
    pack_dim, spike_operand)`` or ``(site, op, pack_dim, spike_operand,
    trailing_lif)``: ``pack_dim`` is the contraction dimension a bit-packed
    implementation would pack (``None`` when the op has no packing
    constraint), ``spike_operand`` (default ``True``) says whether the
    operand a packed impl would pack is {0,1}-valued at that site, and
    ``trailing_lif`` (default ``True``) says whether the site is followed
    by an SN a fused-epilogue impl could absorb. A packed impl with a float
    operand demotes to its dense fallback as an *expected* (structural)
    decision; one whose ``pack_dim % 8 != 0`` is resolved to the same
    fallback as a reported constraint violation. A fused-epilogue impl at a
    no-trailing-LIF site demotes to its pipeline fallback (structural,
    expected); at servable sites it never demotes for packing — the
    megakernel keeps the single launch and the note only records the dense
    arm. All of it is decided *here* — the per-call path then only logs if
    it ever still disagrees (it should not).

    With ``check_registry=True`` every effective implementation must exist
    in the registry, and every override key must match one of the planned
    sites, a dotted group prefix of one (``"tokenizer.conv"`` covers the
    per-stage ``"tokenizer.conv.<i>"`` sites), or a known op name — so a
    typo'd impl *or* a typo'd site fails at policy-validation time rather
    than silently doing nothing.
    """
    rows = []
    for spec in site_specs:
        site, op, dim = spec[0], spec[1], spec[2]
        spike_operand = spec[3] if len(spec) > 3 else True
        trailing_lif = spec[4] if len(spec) > 4 else True
        requested = policy.resolve(site, op)
        effective, notes, violation = requested, [], False
        ragged = dim is not None and dim % 8 != 0
        ffb = fused_epilogue_fallback(op, requested)
        if ffb is not None and not trailing_lif:
            effective = ffb
            notes.append(f"no trailing LIF at this site -> {ffb}")
        fb = packed_fallback(op, effective)
        packed = False
        if fb is not None:
            if not spike_operand:
                effective = fb
                notes.append(f"float (non-spike) operand -> {fb}")
            elif ragged:
                effective = fb
                notes.append(f"pack dim {dim} % 8 != 0 -> {fb}")
                violation = True
            else:
                packed = True
        elif effective in FUSED_EPILOGUE_IMPLS:
            # No demotion: the megakernel's dense arm serves the site in
            # the same single launch; only the packed HBM traffic is lost.
            packed = spike_operand and not ragged
            if not spike_operand:
                notes.append("float (non-spike) operand -> dense arm "
                             "(still fused)")
            elif ragged:
                notes.append(f"pack dim {dim} % 8 != 0 -> dense arm "
                             f"(still fused)")
                violation = True
        note = "; ".join(notes)
        expected = bool(notes) and not violation
        if check_registry:
            get_kernel(op, effective)   # raises on unknown impl
        rows.append(SiteDecision(site, op, requested, effective, note,
                                 expected, packed))
    if check_registry:
        sites = {spec[0] for spec in site_specs}
        known = sites | set(OPS)

        def matches(key: str) -> bool:
            return key in known or any(s.startswith(key + ".")
                                       for s in sites)

        unmatched = [k for k, _ in policy.overrides if not matches(k)]
        if unmatched:
            raise ValueError(
                f"policy overrides {unmatched} match no site, site group or "
                f"op; sites: {sorted(sites)}, ops: {OPS}")
    return rows


_reported_fallbacks: set[tuple[str, str]] = set()


def log_fallbacks(rows: Iterable[SiteDecision]) -> None:
    """Report (once per site+note) every site whose requested impl was
    replaced by its dense fallback at validation time.

    Constraint violations (ragged pack dims) are warnings; *expected*
    structural demotions (``SiteDecision.expected``, e.g. the float-input
    first tokenizer stage) log at INFO so well-shaped configs stay
    warning-free.
    """
    for r in rows:
        if r.note and (r.site, r.note) not in _reported_fallbacks:
            _reported_fallbacks.add((r.site, r.note))
            log = logger.info if r.expected else logger.warning
            log("execution policy: site %s requested %r but %s",
                r.site, r.requested, r.note)


def runtime_fallback(site: str, impl: str, reason: str,
                     expected: bool = False) -> None:
    """Log (once per site+reason) a per-call fallback that validation did
    not predict — e.g. a layer called directly with an odd shape.
    ``expected`` demotes to INFO for structural per-call decisions the plan
    already reported (e.g. the float-input first tokenizer stage)."""
    key = (site, reason)
    if key not in _reported_fallbacks:
        _reported_fallbacks.add(key)
        log = logger.info if expected else logger.warning
        log("execution policy: site %s impl %r fell back at call "
            "time: %s", site, impl, reason)


# ---------------------------------------------------------------------------
# Per-site circuit breaker (guarded dispatch)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BreakerTrip:
    """Record of one tripped dispatch site: which impl raised, what it was
    demoted to, and the stringified error that tripped it."""

    site: str
    op: str
    impl: str
    fallback: str
    error: str


#: site -> trip record. Module-global on purpose: a tripped site stays
#: demoted for the rest of the process (every retrace, every restart of the
#: train loop in-process), exactly like ``_reported_fallbacks``.
_BREAKER_TRIPS: dict[str, BreakerTrip] = {}


def breaker_trips() -> dict[str, BreakerTrip]:
    """Snapshot of every tripped site (empty in a healthy process)."""
    return dict(_BREAKER_TRIPS)


def reset_breaker() -> None:
    """Clear all trips (tests / explicit operator reset)."""
    _BREAKER_TRIPS.clear()


def describe_breaker() -> str:
    """Render the tripped-site table (one line per site; empty string when
    nothing tripped). Appended to ``describe_execution`` output."""
    if not _BREAKER_TRIPS:
        return ""
    lines = ["# circuit breaker: demoted sites",
             "site,op,impl,fallback,error"]
    for site in sorted(_BREAKER_TRIPS):
        t = _BREAKER_TRIPS[site]
        lines.append(f"{t.site},{t.op},{t.impl},{t.fallback},"
                     f"{t.error.splitlines()[0] if t.error else ''}")
    return "\n".join(lines)


def dispatch_site(site: str, op: str, impl: str, invoke: Callable[[], Any],
                  *, fallback_impl: str | None = None,
                  fallback_invoke: Callable[[], Any] | None = None) -> Any:
    """Run ``invoke()`` (the resolved impl for ``site``) behind the per-site
    circuit breaker.

    If the impl raises at dispatch time (Pallas lowering bug, injected
    ``chaos.kernel.<site>`` fault, ...), the site trips: the error is
    logged once, recorded in :func:`breaker_trips` (surfaced by
    ``describe_execution`` and the plan audit), and ``fallback_invoke()`` —
    the jnp reference path for the site — serves this call and every later
    one. With no distinct fallback (the reference impl is already the one
    raising) the error propagates: there is nothing safe to demote to.

    Dispatch runs at trace time (the impls build jax expressions), so a
    plain ``try/except`` is sufficient — no in-jit error plumbing — and a
    trip can only affect traces that have not been cached yet; a fault that
    first manifests *after* a site's trace is cached would surface as a
    runtime error instead, which no breaker can absorb.

    ``fallback_invoke`` exists separately from ``fallback_impl`` because a
    demotion can change the calling convention (the fused-epilogue
    megakernel absorbs the trailing LIF; its fallback is the multi-launch
    pipeline, not a same-signature impl swap) — the call site supplies a
    thunk that knows how to run its own reference path.

    Both thunks run inside ``jax.named_scope(site)``, so every op the site
    builds carries the site's name in its metadata (forward and, as
    ``transpose(jvp(<site>))``, backward), whatever implementation runs
    it. The scope only names: it adds no op to the compiled program.
    """
    import jax

    from repro.chaos import inject as _chaos_inject
    guarded = (fallback_invoke is not None and fallback_impl is not None
               and fallback_impl != impl)
    if guarded and site in _BREAKER_TRIPS:
        with jax.named_scope(site):
            return fallback_invoke()
    try:
        _chaos_inject.kernel_fault(site)
        with jax.named_scope(site):
            return invoke()
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:
        if not guarded:
            raise
        _BREAKER_TRIPS[site] = BreakerTrip(
            site, op, impl, fallback_impl, f"{type(e).__name__}: {e}")
        logger.warning(
            "circuit breaker: site %s impl %r raised at dispatch "
            "(%s: %s) — demoted to %r for the rest of the run",
            site, impl, type(e).__name__, e, fallback_impl)
        with jax.named_scope(site):
            return fallback_invoke()


def dispatch_kernel(site: str, op: str, impl: str, *args: Any) -> Any:
    """Convenience guarded dispatch for the common case where the jnp
    reference impl shares the impl's signature: resolves both through the
    registry and calls with ``*args``."""
    ref = default_impl(op, "jnp")
    return dispatch_site(
        site, op, impl,
        lambda: get_kernel(op, impl)(*args),
        fallback_impl=ref,
        fallback_invoke=(None if impl == ref
                         else lambda: get_kernel(op, ref)(*args)))


# ---------------------------------------------------------------------------
# Kernel registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[tuple[str, str], Callable[..., Any]] = {}


def register_kernel(op: str, impl: str) -> Callable:
    """Decorator: register ``fn`` as the ``impl`` implementation of ``op``.

    Signatures by op (``policy``/``site`` always ride along so nested ops
    can resolve through the same policy):

    * ``lif``:       ``fn(x_seq, cfg: LIFConfig, site) -> spikes``
    * ``lif_state``: ``fn(x_seq, u0, s0, cfg: LIFConfig, site)
                      -> (spikes, (u, s))``
    * ``bn``:        ``fn(params, state, x, train, momentum, eps, policy,
                      site) -> (y, state)``
    * ``linear_bn``: ``fn(params, state, x, train, policy, site)
                      -> (y, state)``
    * ``attn_qk``:   ``fn(q, k, policy, site) -> attn``  (T,B,h,N,M)
    * ``attn_av``:   ``fn(attn, v, policy, site) -> out`` (T,B,h,N,dh)
    * ``conv``:      ``fn(params, state, x, lif_cfg, train, spike_in,
                      policy, site) -> (spikes, new_state)`` — one full
                      eq. 4 tokenizer stage (Conv k3/s2 -> BN -> LIF) on a
                      time-major (T, B, H, W, C) input; ``spike_in`` says
                      whether ``x`` is {0,1}-valued (stage >= 2, or stage 1
                      on pre-encoded spike frames)

    Exception: the ``"fused_epilogue"`` implementation of ``linear_bn``
    absorbs the *following* SN into its single-launch megakernel, so it is
    registered with the extended signature ``fn(params, state, x, lif_cfg,
    train, policy, site) -> (spikes, new_state)`` and is only dispatched
    through ``linear_bn_lif_apply`` (plain ``linear_bn_apply`` demotes it,
    logged, to its pipeline fallback — there is no LIF to fuse there).
    """
    def deco(fn: Callable) -> Callable:
        _REGISTRY[(op, impl)] = fn
        return fn
    return deco


def unregister_kernel(op: str, impl: str) -> None:
    _REGISTRY.pop((op, impl), None)


def available_impls(op: str) -> tuple[str, ...]:
    _ensure_builtins()
    return tuple(sorted(i for (o, i) in _REGISTRY if o == op))


def get_kernel(op: str, impl: str) -> Callable[..., Any]:
    """Look up the registered implementation, importing the builtins first."""
    _ensure_builtins()
    try:
        return _REGISTRY[(op, impl)]
    except KeyError:
        raise KeyError(
            f"no implementation {impl!r} registered for op {op!r}; "
            f"available: {available_impls(op)}") from None


def _ensure_builtins() -> None:
    # The builtin implementations register themselves at import time; pull
    # them in lazily so policy.py never imports the model modules at load
    # (they import *us*).
    import repro.core.spikingformer  # noqa: F401  (imports lif + layers too)


#: Registered impls whose dispatch never launches a Pallas kernel (pure
#: jnp/XLA paths) — the kernel-contract verifier
#: (``repro.analysis.contracts``) requires a ``KernelContract`` declaration
#: for every registered (op, impl) pair NOT named here.
CONTRACT_EXEMPT_IMPLS: frozenset[str] = frozenset({"jnp"})


def registered_kernels() -> tuple[tuple[str, str], ...]:
    """Every registered ``(op, impl)`` pair, builtins imported — the
    contract verifier's coverage universe."""
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Site-table registry (construction-time override validation)
# ---------------------------------------------------------------------------

_SITE_TABLES: dict[str, frozenset[str]] = {}
_SITE_GROUPS: dict[str, frozenset[str]] = {}
_site_tables_loading = False
_site_tables_loaded = False


def register_site_table(model: str, sites: Iterable[str],
                        groups: Iterable[str] = ()) -> None:
    """Declare a model family's site names (plus any group prefixes that
    are valid override keys on their own, e.g. ``"tokenizer.conv"``).

    Models register at import time; :class:`ExecutionPolicy` validates
    override keys against the union of all tables at construction, so a
    typo'd site fails where the policy is *written*, not at plan time (or
    never). Re-registration replaces the model's previous table."""
    _SITE_TABLES[str(model)] = frozenset(str(s) for s in sites)
    _SITE_GROUPS[str(model)] = frozenset(str(g) for g in groups)


def site_tables() -> dict[str, frozenset[str]]:
    """``model -> registered site names`` (builtin tables imported first)."""
    _ensure_site_tables()
    return dict(_SITE_TABLES)


def known_site_keys() -> frozenset[str]:
    """Every valid non-op override key: registered site names, declared
    groups, and every dotted prefix of a registered site."""
    _ensure_site_tables()
    keys: set[str] = set()
    for sites in _SITE_TABLES.values():
        for s in sites:
            keys.add(s)
            while "." in s:
                s = s.rsplit(".", 1)[0]
                keys.add(s)
    for groups in _SITE_GROUPS.values():
        keys.update(groups)
    return frozenset(keys)


def _ensure_site_tables() -> None:
    # The loading flag is a re-entrancy guard: policies constructed *during*
    # these imports skip validation instead of seeing a partial registry.
    global _site_tables_loading, _site_tables_loaded
    if _site_tables_loaded or _site_tables_loading:
        return
    _site_tables_loading = True
    try:
        import repro.core.spikingformer  # noqa: F401  "spikingformer" table
        import repro.core.qkformer       # noqa: F401  "qkformer" table
        import repro.models.lm           # noqa: F401  "lm" table
    finally:
        _site_tables_loading = False
    _site_tables_loaded = True


def _validate_override_keys(overrides: tuple[tuple[str, str], ...]) -> None:
    site_keyed = [k for k, _ in overrides if k not in OPS]
    if not site_keyed or _site_tables_loading:
        return
    known = known_site_keys()
    groups = frozenset().union(*_SITE_GROUPS.values()) if _SITE_GROUPS \
        else frozenset()
    unknown = [k for k in site_keyed
               if k not in known
               and not any(k.startswith(g + ".") for g in groups)]
    if unknown:
        raise ValueError(
            f"ExecutionPolicy overrides {unknown} name no registered site, "
            f"site group or op. Known sites: "
            f"{ {m: sorted(s) for m, s in sorted(_SITE_TABLES.items())} }, "
            f"ops: {OPS}. Pass strict=False for forward-compat site names.")


# ---------------------------------------------------------------------------
# Named policies + environment default
# ---------------------------------------------------------------------------

#: Everything-on policy: fused LIF/BN kernels, the packed (QK^T)V attention
#: path, and the single-launch neuron-layer megakernel (bit-packed/dense
#: matmul + BN + SOMA in ONE Pallas kernel) at every Conv1DBN-with-SN site
#: and every eq. 4 tokenizer stage. Sites with no trailing LIF (Z
#: projection, SMLP-B) demote to the pipeline ``pallas+spike_mm`` arm as a
#: planned structural decision.
_PALLAS_FULL = ExecutionPolicy(
    backend="pallas",
    overrides=(("attn_av", "pallas_packed"), ("attn_qk", "pallas_packed"),
               ("conv", "fused_epilogue"), ("linear_bn", "fused_epilogue")))

NAMED_POLICIES: dict[str, ExecutionPolicy] = {
    "jnp": ExecutionPolicy(),
    "pallas": ExecutionPolicy(backend="pallas"),
    "pallas-full": _PALLAS_FULL,
}


def list_named_policies() -> list[str]:
    return sorted(NAMED_POLICIES)


def named_policy(name: str) -> ExecutionPolicy:
    """Resolve a policy preset name (``jnp``/``pallas``/``pallas-full``)."""
    try:
        return NAMED_POLICIES[name]
    except KeyError:
        raise ValueError(f"unknown policy {name!r}; expected one of "
                         f"{list_named_policies()}") from None


def default_policy() -> ExecutionPolicy:
    """Process-wide default policy, read live from ``REPRO_BACKEND`` so
    ``REPRO_BACKEND=pallas-full pytest`` (or an example run) exercises the
    non-default path without code changes."""
    return named_policy(os.environ.get("REPRO_BACKEND", "jnp"))


# ---------------------------------------------------------------------------
# Legacy-flag shims (PR 1 spellings)
# ---------------------------------------------------------------------------

#: Implementations that only exist under the pallas backend — the legacy
#: shim must drop these when bridging to backend="jnp" (under PR 1
#: semantics, backend="jnp" ran the dense jnp path regardless of spike_mm).
_PALLAS_ONLY_IMPLS = frozenset({"pallas", "pallas+spike_mm", "pallas_packed",
                                "fused_epilogue"})


def policy_from_flags(backend: str | None = None,
                      spike_mm: bool | None = None,
                      interpret: bool | None = None,
                      base: ExecutionPolicy | None = None) -> ExecutionPolicy:
    """Translate the PR 1 ``backend``/``spike_mm``/``interpret`` triple into
    a policy, layered over ``base`` (``None`` keeps the base's value)."""
    base = base if base is not None else ExecutionPolicy()
    ov = dict(base.overrides)
    if spike_mm is True:
        ov["linear_bn"] = "pallas+spike_mm"
    elif spike_mm is False:
        ov.pop("linear_bn", None)
    new_backend = (validate_backend(backend) if backend is not None
                   else base.backend)
    if new_backend == "jnp":
        ov = {k: v for k, v in ov.items() if v not in _PALLAS_ONLY_IMPLS}
    return ExecutionPolicy(
        backend=new_backend,
        interpret=interpret if interpret is not None else base.interpret,
        overrides=tuple(ov.items()),
        strict=base.strict)


def warn_deprecated_flags(what: str, stacklevel: int = 2) -> None:
    """Emit the legacy-flag DeprecationWarning, attributed to *user* code.

    ``stacklevel`` counts the frames between this helper and the user's
    call site: 2 (the default) points at the caller of whatever function
    invoked this — right for the direct shims (``with_backend``,
    ``get_spikingformer_config(backend=...)``). Deeper shims pass their own
    depth (e.g. the frozen-config ``__post_init__`` path adds the dataclass
    ``__init__`` and ``__post_init__`` frames), so the warning filename is
    the user's file, not a repro internal — the shim tests assert this.
    """
    warnings.warn(
        f"{what} is deprecated; pass policy=ExecutionPolicy(...) "
        f"(see docs/EXECUTION.md)", DeprecationWarning,
        stacklevel=stacklevel + 1)


def apply_legacy_exec_flags(cfg: Any, backend: str | None,
                            spike_mm: bool | None,
                            interpret: bool | None) -> None:
    """``__post_init__`` helper for frozen configs that still accept the
    PR 1 kwargs: folds them into ``cfg.policy`` with a DeprecationWarning."""
    if backend is None and spike_mm is None and interpret is None:
        return
    # user -> dataclass __init__ -> __post_init__ -> here: 4 frames up.
    warn_deprecated_flags(
        f"{type(cfg).__name__}(backend=/spike_mm=/interpret=)", stacklevel=4)
    object.__setattr__(cfg, "policy", policy_from_flags(
        backend, spike_mm, interpret, base=cfg.policy))


__all__ = [
    "BACKENDS", "BreakerTrip", "CONTRACT_EXEMPT_IMPLS", "ExecutionPolicy",
    "FUSED_EPILOGUE_IMPLS",
    "NAMED_POLICIES", "OPS", "SiteDecision", "apply_legacy_exec_flags",
    "available_impls", "breaker_trips", "default_impl", "default_policy",
    "describe_breaker", "dispatch_kernel", "dispatch_site",
    "fused_epilogue_fallback", "get_kernel", "known_site_keys",
    "list_named_policies", "log_fallbacks", "named_policy",
    "packed_fallback", "plan_sites", "policy_from_flags", "register_kernel",
    "register_site_table", "registered_kernels", "reset_breaker",
    "runtime_fallback",
    "site_tables", "unregister_kernel", "warn_deprecated_flags",
]
