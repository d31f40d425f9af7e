"""Device time by the program's named scopes (bench/scopes.py): the name
stack of a device op, on hand-made events, on a v5e trace recorded before
the program named its scopes and on one recorded after; the summary of
the older trace pinned to the value; and the reader of the program's span
registry.

``data/pf_tiny_scoped.xplane.pb`` was recorded as ``pf_tiny.xplane.pb``
was (see ``data/README.md``: the harness's traced window at the tiny
size, ``pallas-full`` arm, one TPU v5e, about 0.05 s), after the program
named its scopes (``jax.named_scope`` per dispatch site and around the
tokenizer, the blocks, the head and the optimizer) and its host spans
(``data.place``), from a fresh compile."""
from pathlib import Path

import pytest

from bench import scopes
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
SITES = {"tokenizer.conv.0", "tokenizer.conv.1", "pssa.lif", "pssa.qkv",
         "attn_qk", "attn_av", "pssa.proj", "smlp.lif", "smlp.a", "smlp.b"}


@pytest.mark.parametrize("stack, path", [
    ("jit(train_step)/transpose(jvp(blocks))/while/body/closed_call/"
     "pssa.qkv/dot_general:", "blocks/pssa.qkv"),
    ("jit(train_step)/jvp(tokenizer)/tokenizer.conv.0/jit(bn_fwd)",
     "tokenizer/tokenizer.conv.0"),
    ("jit(train_step)/transpose(jvp(blocks))/while/body/closed_call/"
     "transpose;attn_av/tbhnm,tbhmd->tbhnd/transpose", "blocks/attn_av"),
    ("jit(train_step)/optimizer/is_finite:", "optimizer"),
    ("jit(train_step)/jvp(head)/jit(log_softmax)/reduce_max", "head"),
    ("jit(f)/jvp(blocks)/while/body/checkpoint/rematted_computation/"
     "site/tanh", "blocks/site"),
    ("jit(train_step)/transpose(jvp(blocks))/while/body/closed_call/"
     "transpose(jvp(blocks))/pssa.lif/jit(lif_scan)/while/body/mul:",
     "blocks/pssa.lif"),
    ("jit(train_step)/jvp()/while/body/cond/branch_1_fun/"
     "dynamic_update_slice:", ""),
    ("jit(train_step)/transpose(jvp(tmc,tmk->ck))/dot_general:", ""),
    ("", ""),
])
def test_scope_path_keeps_the_named_scopes(stack, path):
    assert scopes.scope_path(stack) == path


def hand_made():
    """Two steps of 100 ns on one chip: tokenizer 10 ns, a site in the
    blocks 20 ns, the block scan's own op 15 ns, optimizer 5 ns, and an op
    under no scope 5 ns, inside a 60 ns ``while``; one op after the
    window."""
    evs = []
    for k in range(2):
        t = 1000 + 100 * k
        evs += [(op, stack, t + a, t + b) for op, a, b, stack in [
            ("while", 30, 90, "jit(s)/jvp(blocks)/while"),
            ("fusion", 30, 40, "jit(s)/jvp(tokenizer)/tokenizer.conv.0/mul:"),
            ("lif_soma_fwd", 40, 60,
             "jit(s)/jvp(blocks)/while/body/pssa.lif/jit(lif_soma_fwd)"),
            ("dynamic-update-slice", 60, 75,
             "jit(s)/transpose(jvp(blocks))/while/body/dynamic_update_slice:"),
            ("fusion", 75, 80, "jit(s)/optimizer/mul:"),
            ("copy", 80, 85, "")]]
    evs.append(("fusion", "jit(s)/jvp(head)/mul:", 5000, 5010))
    return {"/device:TPU:0": evs}, (1000, 1200)


def test_scope_seconds_of_hand_made_events():
    s = scopes.scope_seconds(*hand_made())
    assert s == pytest.approx({
        "tokenizer/tokenizer.conv.0": 20e-9, "blocks/pssa.lif": 40e-9,
        "blocks": 30e-9, "optimizer": 10e-9, "": 10e-9})
    assert scopes.under(s, "blocks") == pytest.approx(70e-9)
    assert scopes.under(s, "blocks", exclude={"pssa.lif"}) == \
        pytest.approx(30e-9)
    assert scopes.under(s, "tokenizer") == pytest.approx(20e-9)
    assert scopes.under(s, "head") is None


def test_scope_seconds_are_per_chip():
    devices, window = hand_made()
    devices["/device:TPU:1"] = devices["/device:TPU:0"]
    assert scopes.scope_seconds(devices, window)["blocks"] == \
        pytest.approx(30e-9)


def test_a_trace_from_before_the_scopes_reads_as_unscoped():
    """pf_tiny.xplane.pb: every op has a name stack, and none names a
    scope."""
    devices, window = scopes.load(str(DATA / "pf_tiny.xplane.pb"))
    evs = devices["/device:TPU:0"]
    assert all(st.startswith("jit(train_step)/") for _, st, _, _ in evs
               if st)
    s = scopes.scope_seconds(devices, window)
    assert set(s) == {""}
    assert scopes.under(s, "blocks") is None


@pytest.fixture(scope="module")
def scoped():
    return scopes.load(str(DATA / "pf_tiny_scoped.xplane.pb"))


def test_a_recorded_trace_names_every_scope(scoped):
    s = scopes.scope_seconds(*scoped)
    names = set().union(*(p.split("/") for p in s))
    assert {"tokenizer", "blocks", "head", "optimizer"} <= names
    assert SITES <= names
    for path in s:
        parts = path.split("/")
        if parts[-1].startswith("tokenizer."):
            assert parts[0] == "tokenizer", path
        elif parts[-1] in SITES:
            assert parts[0] == "blocks", path


def test_the_coarse_scopes_cover_the_recorded_step(scoped):
    """The four coarse scopes hold at least 99% of the device time of the
    recorded pallas-full step; the block scan's own ops are a part of the
    blocks' time."""
    s = scopes.scope_seconds(*scoped)
    total = sum(s.values())
    coarse = sum(scopes.under(s, k) for k in ("tokenizer", "blocks",
                                              "head", "optimizer"))
    assert coarse == pytest.approx(total - s.get("", 0.0))
    assert coarse >= 0.99 * total
    scan = scopes.under(s, "blocks", exclude=SITES)
    assert 0 < scan < scopes.under(s, "blocks")


def test_program_spans_share_the_device_clock(scoped):
    """Every ``data.place`` span of the recorded run lies inside the
    harness's ``bench.place`` on the same Python thread, and the window's
    device ops fall between the first and last step's spans."""
    from jax.profiler import ProfileData

    events = {"data.place": [], "bench.place": []}
    for plane in ProfileData.from_file(
            str(DATA / "pf_tiny_scoped.xplane.pb")).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in events:
                    events[e.name].append((line.name, e.start_ns, e.end_ns))
    assert len(events["data.place"]) == len(events["bench.place"]) >= 3
    for line, s, e in events["data.place"]:
        assert any(ln == line and bs <= s and e <= be
                   for ln, bs, be in events["bench.place"])
    devices, (lo, hi) = scoped
    assert any(lo <= s < hi for _, _, s, _ in devices["/device:TPU:0"])


def test_data_place_reader_reads_the_program_span_registry():
    from bench.cells import metric_reader
    from repro.analysis.tracing import reset_spans, span
    read = metric_reader("data.place_ms")
    reset_spans()
    try:
        assert read(None) is None
        for _ in range(4):
            with span("data.place"):
                pass
        assert 0 < read(None) < 1.0
    finally:
        reset_spans()


def test_the_recorded_trace_keeps_its_summary():
    """Every field of the older recorded trace's summary, to the value,
    so that a change to the reduction shows (pf_tiny.xplane.pb)."""
    s = tr.summarize(tr.load(str(DATA / "pf_tiny.xplane.pb")))
    assert s.steps == 10
    assert s.window_s == 0.051006918
    assert s.busy_s == 0.004068585
    assert s.exposed_collective_s == 0.0
    assert s.span_s == pytest.approx(
        {"bench.batch": 0.00590227, "bench.place": 0.015053979,
         "bench.dispatch": 0.015530998, "bench.read": 0.013813311},
        rel=1e-12)
    assert s.family_s == pytest.approx(
        {"neuron_layer": 0.000233153, "lif": 0.000299382,
         "spike_mm": 0.00042367, "bn": 0.000208245}, rel=1e-12)
    assert len(s.ops) == 60
    assert [n for n, _ in s.ops[:10]] == [
        "fusion", "copy", "spike_matmul_packed_batched", "concatenate",
        "neuron_layer_train", "broadcast_select_fusion", "bn_bwd",
        "is-finite_reduce_fusion", "lif_soma_bwd", "lif_soma_fwd"]
    assert [v for _, v in s.ops[:3]] == pytest.approx(
        [0.001069352, 0.000675049, 0.000325538], rel=1e-12)
    assert s.gaps[0] == ("bench.read", 0.005080026)
    assert s.gaps[-1] == ("bench.dispatch", 0.003092447)
    assert [g for g, _ in s.gaps] == ["bench.read"] * 9 + ["bench.dispatch"]


@pytest.fixture(scope="module")
def scoped_summary():
    return tr.summarize(tr.load(str(DATA / "pf_tiny_scoped.xplane.pb")))


def test_the_summary_carries_device_time_per_scope(scoped, scoped_summary):
    """``trace.summarize`` reads the name stacks of the file it summarizes;
    events handed to it without a file carry no scopes."""
    assert scoped_summary.scope_s == scopes.scope_seconds(*scoped)
    data = tr.load(str(DATA / "pf_tiny_scoped.xplane.pb"))
    data.path = None
    assert tr.summarize(data).scope_s == {}


@pytest.mark.parametrize("metric, scope, ms", [
    ("model.tokenizer_ms", "tokenizer", 0.2021095),
    ("model.blocks_ms", "blocks", 0.1601065),
    ("train.optimizer_ms", "optimizer", 0.0390874),
])
def test_scope_readers_read_the_recorded_traces(scoped_summary, metric,
                                                scope, ms):
    """Milliseconds per step under the scope in the recorded scoped trace
    (10 steps); nothing in the trace recorded before the scopes."""
    from types import SimpleNamespace

    from bench.cells import metric_reader

    read = metric_reader(metric)
    value = read(SimpleNamespace(trace=scoped_summary))
    assert value == pytest.approx(
        1e3 * scopes.under(scoped_summary.scope_s, scope)
        / scoped_summary.steps, rel=1e-12)
    assert value == pytest.approx(ms, rel=1e-9)
    unscoped = tr.summarize(tr.load(str(DATA / "pf_tiny.xplane.pb")))
    assert read(SimpleNamespace(trace=unscoped)) is None
