"""Benchmark entry point — one section per paper table/figure.

Prints ``name,value,...`` CSV blocks:
  table1   - model OPs/energy comparison + backend A/B   (Table I)
  fig9_10  - nine-dataflow energy+latency sweep          (Fig. 9 / Fig. 10)
  fig11    - OS_C per-operator energy breakdown          (Fig. 11)
  table9   - headline metrics vs paper + SOTA            (Table IX)
  kernels  - Pallas kernel micro-benches                 (interpret mode)
  serving  - continuous-batching Poisson-trace replay    (docs/SERVING.md)
  energy   - per-site analytic energy/latency with measured sparsity and
             oracle block picks (docs/AUTOTUNE.md; fully deterministic)

``--smoke`` (used by CI) shrinks the kernel shapes and rep counts so the
whole sweep finishes in well under a minute on a laptop-class CPU.

``--json PATH`` additionally writes every section's rows as machine-readable
JSON (``sections`` -> section -> metric -> value), so the perf trajectory is
trackable across PRs; the CI bench-smoke legs upload it as an artifact.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

# Allow both `python -m benchmarks.run` and `python benchmarks/run.py`.
_ROOT = Path(__file__).resolve().parent.parent
for p in (str(_ROOT), str(_ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _coerce(field: str):
    """CSV field -> float where possible (ints included), else the string."""
    try:
        return float(field)
    except ValueError:
        return field


def parse_section(lines: list[str]) -> dict:
    """CSV block lines -> {metric: value} rows.

    A section is blank-line-separated blocks; each block's first line is a
    header and each data row keys on its first field. Values: the row's
    remaining fields mapped by header column (collapsed to a scalar when
    there is exactly one). ``#``-comment lines are skipped; duplicate
    metric names across blocks (e.g. the per-policy dispatch tables of
    ``table1``) disambiguate with a ``#<n>`` suffix so nothing is dropped.
    """
    out: dict = {}
    header: list[str] | None = None
    for line in lines:
        line = line.strip()
        if not line or line.startswith("#"):
            header = None          # blank/comment ends the current block
            continue
        fields = line.split(",")
        if header is None:
            header = fields
            continue
        if len(fields) > len(header):
            # Comma-valued last column (e.g. a PartitionSpec in the
            # sharding table): re-join the overflow so nothing is lost.
            fields = fields[:len(header) - 1] + \
                [",".join(fields[len(header) - 1:])]
        key, rest = fields[0], fields[1:]
        cols = header[1:len(rest) + 1]
        value = (_coerce(rest[0]) if len(rest) == 1 else
                 {c: _coerce(v) for c, v in zip(cols, rest)})
        name, n = key, 2
        while name in out:
            name, n = f"{key}#{n}", n + 1
        out[name] = value
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes/reps; still exercises every section")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write section->metric->value JSON to PATH")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_autotune, bench_comparison,
                            bench_dataflows, bench_energy_breakdown,
                            bench_kernels, bench_model_table, bench_serving)
    sections = [
        ("table1", lambda: bench_model_table.run(smoke=args.smoke)),
        ("fig9_10", bench_dataflows.run),
        ("fig11", bench_energy_breakdown.run),
        ("table9", bench_comparison.run),
        ("kernels", lambda: bench_kernels.run(smoke=args.smoke)),
        ("serving", lambda: bench_serving.run(smoke=args.smoke)),
        ("energy", lambda: bench_autotune.energy_section(smoke=args.smoke)),
    ]
    report = {"smoke": args.smoke, "generated_unix": int(time.time()),
              "sections": {}}
    for name, fn in sections:
        t0 = time.perf_counter()
        lines = fn()
        dt = time.perf_counter() - t0
        print(f"== {name} ({dt:.1f}s) ==")
        print("\n".join(lines))
        print()
        report["sections"][name] = parse_section(lines)
        report["sections"][name]["_section_seconds"] = round(dt, 2)
    if args.json:
        Path(args.json).write_text(json.dumps(report, indent=1,
                                              sort_keys=True))
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
