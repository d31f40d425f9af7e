"""Operations and bytes of one train step, counted from the configuration's
shapes (never from what a kernel happens to do).

``step_flops`` is the model's algorithm: 2 x the multiply-adds of the
tokenizer convolutions, the Q/K/V/Z and MLP projections, Q K^T, (Q K^T) V
and the head, times 3 for the forward and backward passes. Recomputation
is not counted, and no term depends on which implementation runs a site.

``family_work`` counts, for one kernel family, the work of the sites that
the step's execution plan gives to that family, as (flops, bytes) per
launch-sized piece, so that each piece's roofline time is the larger of
flops over peak and bytes over bandwidth:

* ``lif`` -- per neuron and time step, the forward reads X and writes S, and
  the backward reads the spike cotangent and the membrane potential and
  writes dX: 5 float32 words, 20 bytes. The few operations per element are
  negligible beside that, so these sites are bound by memory.
* ``spike_mm`` -- forward products with a {0,1} operand (the backward runs as
  dense XLA matmuls). A {0,1} operand counts 1 bit per element, the least
  any implementation must move; dense operands and outputs count 4 bytes.
"""
from __future__ import annotations

WORD = 4          # float32 bytes


def tokenizer_stages(model: dict) -> list[tuple[int, int, int]]:
    """(c_in, c_out, output side) of each eq. 4 stage."""
    n = model["image_size"] // model["patch_grid"]
    stages = max(1, n.bit_length() - 1)
    out, c_in, side = [], model["in_channels"], model["image_size"]
    for i in range(stages):
        c_out = model["d_model"] // 2 ** (stages - 1 - i)
        side //= 2
        out.append((c_in, c_out, side))
        c_in = c_out
    return out


def step_flops(model: dict, batch: int) -> float:
    t, d, f = model["time_steps"], model["d_model"], model["d_ff"]
    n = model["patch_grid"] ** 2
    macs = sum(t * batch * side * side * 9 * c_in * c_out
               for c_in, c_out, side in tokenizer_stages(model))
    rows = t * batch * n
    per_layer = 4 * rows * d * d + 2 * rows * d * f + 2 * t * batch * n * n * d
    macs += model["num_layers"] * per_layer + batch * d * model["num_classes"]
    return 2.0 * 3.0 * macs


def _lif_pieces(model, batch, plan):
    t, d, f, layers = (model["time_steps"], model["d_model"], model["d_ff"],
                       model["num_layers"])
    rows = t * batch * model["patch_grid"] ** 2
    fused = lambda site: plan.get(site) == "fused_epilogue"  # noqa: E731
    elems = []
    for i, (_, c_out, side) in enumerate(tokenizer_stages(model)):
        if plan.get("tokenizer.lif") == "pallas" and not fused(
                f"tokenizer.conv.{i}"):
            elems.append(t * batch * side * side * c_out)
    per_layer = []
    if plan.get("pssa.lif") == "pallas":
        per_layer += [rows * d] * (2 if fused("pssa.qkv") else 5)
    if plan.get("smlp.lif") == "pallas":
        per_layer += [rows * d] + ([] if fused("smlp.a") else [rows * f])
    elems += per_layer * layers
    return [(0.0, 5.0 * WORD * e) for e in elems]


def _mm(m, c, k, spike_a=True, spike_b=False):
    """One forward (m, c) x (c, k) product with {0,1} operands as flagged."""
    a = m * c / 8 if spike_a else m * c * WORD
    b = c * k / 8 if spike_b else c * k * WORD
    return (2.0 * m * c * k, a + b + m * k * WORD)


def _spike_mm_pieces(model, batch, plan):
    t, d, f, h, layers = (model["time_steps"], model["d_model"], model["d_ff"],
                          model["n_heads"], model["num_layers"])
    n = model["patch_grid"] ** 2
    rows = t * batch * n
    spike_mm = lambda site: plan.get(site) == "pallas+spike_mm"  # noqa: E731
    pieces = []
    spike_in = model["spike_input"]
    for i, (c_in, c_out, side) in enumerate(tokenizer_stages(model)):
        if (plan.get(f"tokenizer.conv.{i}") == "pallas_packed" and spike_in
                and (9 * c_in) % 8 == 0):
            pieces += [_mm(batch * side * side, 9 * c_in, c_out)] * t
        spike_in = True
    layer = []
    if spike_mm("pssa.qkv"):
        layer += [_mm(rows, d, d)] * 3
    if spike_mm("pssa.proj"):
        layer += [_mm(rows, d, d)]
    if spike_mm("smlp.a"):
        layer += [_mm(rows, d, f)]
    if spike_mm("smlp.b"):
        layer += [_mm(rows, f, d)]
    dh = d // h
    if plan.get("attn_qk") == "pallas_packed" and dh % 8 == 0:
        layer += [_mm(n, dh, n, spike_b=True)] * (t * batch * h)
    if plan.get("attn_av") == "pallas_packed" and n % 8 == 0:
        layer += [_mm(dh, n, n)] * (t * batch * h)
    return pieces + layer * layers


FAMILIES = {"lif": _lif_pieces, "spike_mm": _spike_mm_pieces}


def family_work(family: str, model: dict, batch: int,
                plan: dict[str, str]) -> list[tuple[float, float]]:
    """(flops, bytes) of each piece of ``family``'s work in one step, for
    ``plan`` (site -> implementation that runs it)."""
    return FAMILIES[family](model, batch, plan)


def roofline_seconds(pieces, peaks: dict) -> float:
    """Least time for the pieces: per piece the larger of flops over peak
    and bytes over bandwidth."""
    return sum(max(fl / peaks["bf16_flops_per_s"],
                   by / peaks["hbm_bytes_per_s"]) for fl, by in pieces)
