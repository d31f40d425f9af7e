"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix
and limits are files under ``bench/`` found by name (``bench/cells.py``).
The last line of standard output is the result as one JSON object; the
numbers compared with the reference are also the last lines of standard
error. Without the TPU chips the cell asks for, the run exits non-zero
and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import cells, device, harness

    cell = cells.find_cell(args.workload)
    devices = device.require_tpus(cell.chips)
    peaks = device.peaks_for(devices[0].device_kind)

    import jax
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.trace:
        # per-scope readings need executables compiled with their name
        # stacks; runs without the trace keep the keys they always had
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peaks, T0)
    harness.report(out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
