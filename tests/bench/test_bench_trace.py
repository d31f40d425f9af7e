"""Trace reduction (bench/trace.py): on hand-made events, and on traces a
v5e recorded through the harness (tests/bench/data, see its README)."""
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
FAMILIES = tr.kernel_families()


def test_op_names_drop_the_instruction_numbers():
    assert tr.op_name('%fusion.393 = (f32[32,56,56,64]) fusion(...)') == \
        "fusion"
    assert tr.op_name("%lif_soma_fwd.84 = (f32[4]) custom-call()") == \
        "lif_soma_fwd"
    assert tr.op_name("%broadcast.120.clone = f32[] broadcast()") == \
        "broadcast"
    assert tr.op_name("%all-reduce-start.3 = f32[] all-reduce-start()") == \
        "all-reduce-start"


def test_union_and_difference_of_intervals():
    u = tr._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert tr._length(u) == 7
    assert tr._minus([(0, 10)], [(2, 3), (5, 8)]) == 6
    assert tr._minus([(0, 2), (4, 6)], []) == 4


def synthetic():
    """Two steps of 100 ns each on two chips; the device is busy 60 ns a
    step on chip 0 and 40 ns on chip 1; a collective half hidden."""
    spans, devices = [], {"/device:TPU:0": [], "/device:TPU:1": []}
    for k in range(2):
        t = 1000 + 100 * k
        spans += [("bench.batch", t, t + 20), ("bench.place", t + 20, t + 25),
                  ("bench.dispatch", t + 25, t + 30),
                  ("bench.read", t + 30, t + 100)]
        devices["/device:TPU:0"] += [
            ("while", t + 30, t + 90, False),
            ("lif_soma_fwd", t + 30, t + 50, True),
            ("fusion", t + 50, t + 70, False),
            ("all-reduce", t + 60, t + 90, False)]
        devices["/device:TPU:1"] += [
            ("spike_matmul_packed", t + 40, t + 80, True)]
    runtime = [("XlaLinearize", 1000, 1025)]
    return tr.TraceData(devices, sorted(spans, key=lambda s: s[1]), runtime)


def test_summary_of_hand_made_events():
    s = tr.summarize(synthetic(), FAMILIES)
    assert s.steps == 2
    assert s.window_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx((120 + 80) / 2 * 1e-9)
    assert s.family_s["lif"] == pytest.approx(40e-9)
    assert s.family_s["spike_mm"] == pytest.approx(80e-9)
    # chip 0 is the busiest: all-reduce 60..90, compute to 70 -> 20 exposed
    assert s.exposed_collective_s == pytest.approx(40e-9)
    assert s.span_s["bench.batch"] == pytest.approx(40e-9)
    assert dict(s.ops)["fusion"] == pytest.approx(20e-9)
    assert "while" not in dict(s.ops)
    # chip 1 idles from 80 ns into a step to 40 ns into the next, while
    # the host makes the next batch
    assert s.gaps[0] == ("bench.batch", pytest.approx(60e-9))
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_a_complete_step_is_an_error():
    data = tr.TraceData({}, [("bench.batch", 0, 1)], [])
    with pytest.raises(RuntimeError, match="no complete bench step"):
        tr.summarize(data, FAMILIES)


@pytest.fixture(scope="module")
def one_chip():
    return tr.load(str(DATA / "pf_tiny.xplane.pb"))


def test_a_recorded_one_chip_trace_loads(one_chip):
    assert list(one_chip.devices) == ["/device:TPU:0"]
    names = {n for n, *_ in one_chip.devices["/device:TPU:0"]}
    kernels = {n for n, _, _, k in one_chip.devices["/device:TPU:0"] if k}
    assert any("lif_soma_fwd" in n for n in kernels)
    assert any("spike_matmul_packed" in n for n in kernels)
    assert "fusion" in names
    assert {n for n, *_ in one_chip.spans} == set(tr.STEP_SPANS)


def test_a_recorded_one_chip_trace_reduces(one_chip):
    s = tr.summarize(one_chip)
    assert s.steps >= 3
    assert 0 < s.busy_s < s.window_s
    assert s.family_s["lif"] > 0 and s.family_s["spike_mm"] > 0
    assert s.exposed_collective_s == 0
    assert sum(s.span_s.values()) <= s.window_s * (1 + 1e-9)
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(lab.split("|")[0] in tr.STEP_SPANS + ("no span",)
               for lab, _ in b["idle_gaps"])

