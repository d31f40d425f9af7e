"""BENCHMARK.json and the files the harness finds by name; the refusal to
run without the chips a cell asks for."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import cells, device
from bench.compare import NUMBERS
from bench.cells import BENCH, ROOT, load_json

SPEC = load_json(ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files_by_name(name):
    cell = cells.find_cell(name)
    w = next(w for w in SPEC["workloads"] if w["name"] == name)
    assert cell.chips == w["chips"]
    assert cell.model["name"] == w["config"]
    assert cell.limits and set(cell.limits) <= set(NUMBERS)
    assert all(0 < v for v in cell.limits.values())
    assert cell.mix["mesh"]["data"] * cell.mix["mesh"]["model"] == cell.chips
    assert {m["name"] for m in cell.end_to_end} == {
        m["name"] for m in SPEC["end_to_end"]}


def test_an_unknown_cell_names_the_known_ones():
    with pytest.raises(KeyError, match="sf8-512"):
        cells.find_cell("no-such-cell")


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(cells.metric_reader(metric))


def test_benchmark_file_keeps_to_the_contract_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for path in SPEC["paths"]:
        assert (ROOT / path).is_dir()
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_peaks_carry_their_source():
    for kind, peaks in load_json(BENCH / "peaks.json").items():
        assert "Google Cloud" in peaks["source"]
        assert peaks["bf16_flops_per_s"] > 0 and peaks["hbm_bytes_per_s"] > 0


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(device.NoChip, match="no published peaks"):
        device.peaks_for("TPU v99")


def test_a_cpu_platform_is_refused():
    with pytest.raises(device.NoChip, match="'cpu'"):
        device.require_tpus(1)


def test_the_command_on_a_cpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.mark.parametrize("trace", [0, 1])
def test_only_a_traced_run_keys_its_cache_with_debug_information(
        monkeypatch, trace):
    """A ``--trace 1`` run needs executables that carry the program's
    named scopes; an untraced one keeps the cache keys it always had, so
    that its set-up finds what earlier runs compiled."""
    import importlib.util
    from types import SimpleNamespace

    import jax

    from bench import harness

    flag = "jax_compilation_cache_include_metadata_in_key"
    seen = {}

    def fake_run(*args, **kwargs):
        seen["key"] = getattr(jax.config, flag)
        return {"compared": {}}

    monkeypatch.setattr(device, "require_tpus", lambda n: [
        SimpleNamespace(device_kind="TPU v5 lite")])
    monkeypatch.setattr(harness, "run", fake_run)
    spec = importlib.util.spec_from_file_location(
        "bench_run", ROOT / "bench" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    before = getattr(jax.config, flag)
    try:
        assert module.main(["--workload", CELLS[0], "--seed", "1",
                            "--seconds", "1", "--trace", str(trace)]) == 0
    finally:
        jax.config.update(flag, before)
    assert seen["key"] is bool(trace)
