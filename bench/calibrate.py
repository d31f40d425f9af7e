"""The readings behind a cell's limits, taken on the chip at the cell's size.

    python3 bench/calibrate.py --cell <name> [--first-seed 1000] [--out DIR]
    python3 bench/calibrate.py --cell <name> --out DIR --write-limits

For each seed the run is the benchmark's own (set-up, a one-second window,
the reference) with the program's step as it is (the lower readings, on
:data:`SEEDS` seeds), with the bfloat16 reference in its place (the
control), and with each fault of ``bench/faults.py`` that the cell can
have (a state left unchanged; half of the batch left out; on a mesh, the
exchange between chips left out), on :data:`OTHER_SEEDS` seeds each.
Each reading is one JSON line on standard output and in ``DIR``. The
benchmark's own runs never run this.

``--write-limits`` then sets ``bench/limits/<cell>.json`` from the readings
in ``DIR`` by :func:`limits_from`.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


SEEDS, OTHER_SEEDS = 12, 3
WINDOW_S = 1.0
#: Where the limit sits between the lower and the upper reading, on a
#: log scale: past the middle, so that fresh seeds find room above the
#: dozen that set the lower reading.
TOWARD_UPPER = 0.6


def limits_from(lines: list[dict]) -> dict:
    """Each number's lower reading (the largest of the sound runs), its
    upper reading (the smallest of the control's and of a state left
    unchanged where that is 3x the lower or more, and of each other fault
    where that is 10x or more) and the limit between them, 2 significant
    digits. A number with no upper reading gets no limit and is not
    compared."""
    from bench.compare import NUMBERS

    out = {}
    for k in NUMBERS:
        sound = [x[k] for x in lines if x["kind"] == "program" and k in x]
        if not sound:
            continue
        lower = max(sound)
        ups = []
        for kind in {x["kind"] for x in lines} - {"program"}:
            vals = [x[k] for x in lines if x["kind"] == kind and k in x]
            need = 3 if kind in ("control", "unchanged") else 10
            if vals and min(vals) >= need * lower:
                ups.append(min(vals))
        if not ups:
            out[k] = {"lower": lower, "upper": None, "limit": None}
            continue
        upper = min(ups)
        limit = math.exp(math.log(lower) + TOWARD_UPPER
                         * (math.log(upper) - math.log(lower)))
        out[k] = {"lower": lower, "upper": upper,
                  "limit": float(f"{limit:.2g}")}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--out", default=None)
    ap.add_argument("--write-limits", action="store_true")
    args = ap.parse_args(argv)
    if args.write_limits:
        return write_limits(args.cell, args.out)

    from bench import cells, device, faults, harness
    from bench.compare import NUMBERS

    # every number is read, whether or not the cell compares it yet
    cell = dataclasses.replace(cells.find_cell(args.cell),
                               limits={k: math.inf for k in NUMBERS})
    devices = device.require_tpus(cell.chips)
    peaks = device.peaks_for(devices[0].device_kind)

    import jax
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    kinds = [("program", None, SEEDS),
             ("control", faults.control(cell), OTHER_SEEDS),
             ("half_batch", faults.half_batch, OTHER_SEEDS),
             ("unchanged", faults.unchanged, OTHER_SEEDS)]
    if cell.chips > 1:
        kinds.append(("no_exchange", faults.no_exchange(cell),
                       OTHER_SEEDS))
    throughput = cell.family.THROUGHPUT
    out = None
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        out = open(os.path.join(args.out, f"{cell.name}.jsonl"), "a")
    try:
        for kind, hook, count in kinds:
            for i in range(count):
                seed = args.first_seed + i
                t = time.perf_counter()
                try:
                    res = harness.run(cell, seed, WINDOW_S, False, devices,
                                      peaks, t, step_hook=hook)
                    line = {"kind": kind, "seed": seed,
                            **{k: v["value"] for k, v in
                               res["compared"].items()},
                            "setup_s": res["metrics"]["setup_s"]["value"],
                            throughput:
                                res["metrics"][throughput]["value"]}
                except Exception as e:  # a crashing control has failed
                    line = {"kind": kind, "seed": seed, "error": repr(e)}
                line["wall_s"] = time.perf_counter() - t
                print(json.dumps(line), flush=True)
                if out:
                    out.write(json.dumps(line) + "\n")
                    out.flush()
    finally:
        if out:
            out.close()
    print(f"[calibrate] {time.perf_counter() - T0:.1f} s", flush=True)
    return 0


def write_limits(cell: str, directory: str) -> int:
    """``bench/limits/<cell>.json`` from the readings in ``directory``."""
    with open(os.path.join(directory, f"{cell}.jsonl")) as f:
        lines = [json.loads(x) for x in f if "error" not in json.loads(x)]
    found = limits_from(lines)
    limits = {k: v["limit"] for k, v in found.items()
              if v["limit"] is not None}
    with open(ROOT / "bench" / "limits" / f"{cell}.json", "w") as f:
        f.write(json.dumps(limits) + "\n")
    print(json.dumps(found), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
