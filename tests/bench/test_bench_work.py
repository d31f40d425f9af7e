"""The benchmark's operation and byte counts: the Spikingformer family's
(bench/families/spikingformer.py) and the roofline (bench/work.py)."""
import pytest

from bench import work
from bench.cells import BENCH, family, load_json

PEAKS = load_json(BENCH / "peaks.json")["TPU v5 lite"]
SF = family("spikingformer")
SF8 = load_json(BENCH / "configs" / "spikingformer-8-512.json")
DVS = load_json(BENCH / "configs" / "spikingformer-2-256-dvs.json")

#: The plan of the pallas-full arm at batch 8 on a v5e: every fused site
#: demoted to its pipeline arm, attention's AV unpacked (N = 196).
PALLAS_FULL_B8 = {
    "tokenizer.conv.0": "pallas", "tokenizer.conv.1": "pallas_packed",
    "tokenizer.conv.2": "pallas_packed", "tokenizer.conv.3": "pallas_packed",
    "tokenizer.lif": "pallas", "pssa.lif": "pallas", "smlp.lif": "pallas",
    "pssa.qkv": "pallas+spike_mm", "pssa.proj": "pallas+spike_mm",
    "smlp.a": "pallas+spike_mm", "smlp.b": "pallas+spike_mm",
    "attn_qk": "pallas_packed", "attn_av": "jnp"}


def hand_count(config, b):
    """The hand count of one train step's model FLOPs."""
    m = config["model"]
    t, d, f, n = m["time_steps"], m["d_model"], m["d_ff"], m["patch_grid"] ** 2
    convs = [(3, 64, 112), (64, 128, 56), (128, 256, 28), (256, 512, 14)]
    macs = sum(t * b * s * s * 9 * ci * co for ci, co, s in convs)
    layer = t * b * n * (4 * d * d + 2 * d * f) + 2 * t * b * 8 * n * n * 64
    macs += 8 * layer + b * d * 1000
    return 6 * macs


def test_step_flops_match_hand_count_for_spikingformer_8_512():
    flops = SF.step_flops(SF8, 8)
    assert flops == hand_count(SF8, 8)
    assert flops == pytest.approx(1.14e12, rel=0.01)
    # 5.8 ms of a v5e's bf16 peak
    assert flops / PEAKS["bf16_flops_per_s"] == pytest.approx(5.8e-3, rel=0.01)


@pytest.mark.parametrize("batch", [1, 8, 16])
def test_step_flops_scale_with_batch(batch):
    head = 2 * 3 * batch * SF8["model"]["d_model"] * SF8["model"][
        "num_classes"]
    per_image = (SF.step_flops(SF8, 8) - 2 * 3 * 8 * 512 * 1000) / 8
    assert SF.step_flops(SF8, batch) == pytest.approx(
        per_image * batch + head)


def test_tokenizer_stages_follow_the_configuration():
    assert SF.tokenizer_stages(SF8["model"]) == [
        (3, 64, 112), (64, 128, 56), (128, 256, 28), (256, 512, 14)]
    assert SF.tokenizer_stages(DVS["model"]) == [
        (2, 32, 64), (32, 64, 32), (64, 128, 16), (128, 256, 8)]


def test_lif_work_counts_every_standalone_lif_site():
    pieces = SF.kernel_work("lif", SF8, 8, PALLAS_FULL_B8)
    # 4 tokenizer stages + 8 layers x (5 PSSA + 2 SMLP) LIF scans
    assert len(pieces) == 4 + 8 * 7
    rows = 4 * 8 * 196
    assert sum(b for _, b in pieces) == 20 * (
        4 * 8 * (112**2 * 64 + 56**2 * 128 + 28**2 * 256 + 14**2 * 512)
        + 8 * rows * (6 * 512 + 2048))
    # 20 bytes and no FLOPs per neuron-step: bound by memory
    assert all(f == 0 for f, _ in pieces)


def test_lif_work_leaves_out_sites_a_megakernel_absorbs():
    plan = dict(PALLAS_FULL_B8, **{"pssa.qkv": "fused_epilogue",
                                   "smlp.a": "fused_epilogue"})
    assert len(SF.kernel_work("lif", SF8, 8, plan)) == 4 + 8 * 3


def test_no_family_work_on_the_jnp_arm():
    plan = {site: "jnp" for site in PALLAS_FULL_B8}
    assert SF.kernel_work("lif", SF8, 8, plan) == []
    assert SF.kernel_work("spike_mm", SF8, 8, plan) == []


def test_spike_mm_work_counts_packed_products_only():
    pieces = SF.kernel_work("spike_mm", SF8, 8, PALLAS_FULL_B8)
    # 3 packed conv stages x T, per layer 6 projections + T*B*h Q K^T
    assert len(pieces) == 3 * 4 + 8 * (6 + 4 * 8 * 8)
    fl = sum(f for f, _ in pieces)
    rows = 4 * 8 * 196
    assert fl == 2 * (4 * 8 * (56**2 * 576 * 128 + 28**2 * 1152 * 256
                               + 14**2 * 2304 * 512)
                      + 8 * (rows * (4 * 512 * 512 + 2 * 512 * 2048)
                             + 4 * 8 * 8 * 196 * 64 * 196))


def test_roofline_takes_the_larger_bound_per_piece():
    compute = (PEAKS["bf16_flops_per_s"], 1.0)        # 1 s of FLOPs
    memory = (1.0, PEAKS["hbm_bytes_per_s"] * 2)       # 2 s of bytes
    assert work.roofline_seconds([compute, memory], PEAKS) == pytest.approx(3)
