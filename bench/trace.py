"""From a profiler trace of a ``--trace 1`` run to the numbers the per-layer
readers read.

The JAX profiler writes one ``.xplane.pb``. On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per HLO
instruction run, named by the instruction's text (``%lif_soma_fwd.84 =
... custom-call(...), custom_call_target="tpu_custom_call" ...``). The
host is the plane ``/host:CPU``: the harness's spans (``bench.batch``,
``bench.place``, ``bench.dispatch``, ``bench.read``) sit on the Python
thread, and the runtime's own work (transfers, layout changes) on its
threads. Host and device events share one clock.

The traced window runs from the first traced step's ``bench.batch`` to the
last step's ``bench.read``; only events that start inside it count. Device
time by the program's named scopes comes from the same file
(``bench/scopes.py``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

from bench.cells import BENCH, load_json

#: Instructions that contain others on the same line (a scan's ``while``).
CONTAINERS = ("while", "conditional", "call")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
STEP_SPANS = ("bench.batch", "bench.place", "bench.dispatch", "bench.read")


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] ...`` -> ``fusion``."""
    head = text.split(" ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+(\.clone)?$", "", head)


def is_kernel(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


@dataclasses.dataclass
class TraceData:
    """Events as (name, start_ns, end_ns): per chip the ``XLA Ops`` line
    (name is the op name, with a flag for Pallas kernels), and the host's
    Python-thread spans and runtime-thread events."""
    devices: dict            # plane name -> [(op, start, end, is_kernel)]
    spans: list              # [(name, start, end)] of the harness
    runtime: list            # [(name, start, end)] other host threads
    path: str | None = None  # the .xplane.pb read, for the name stacks


def load(path: str) -> TraceData:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans, runtime = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs = [(op_name(e.name), e.start_ns, e.end_ns,
                            is_kernel(e.name)) for e in line.events]
                    if evs:
                        devices[plane.name] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    item = (e.name, e.start_ns, e.end_ns)
                    if e.name in STEP_SPANS:
                        spans.append(item)
                    elif not line.name.startswith("python"):
                        runtime.append(item)
    spans.sort(key=lambda s: s[1])
    return TraceData(devices, spans, runtime, path)


def load_dir(directory: str) -> TraceData:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {files}")
    return load(files[0])


def _union(intervals) -> list[tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _minus(a, b) -> float:
    """Length of the union ``a`` not covered by the union ``b``."""
    covered, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return _length(a) - covered


def kernel_families() -> dict[str, list[str]]:
    """family -> substrings of kernel entry names (``bench/kernels.json``)."""
    return load_json(BENCH / "kernels.json")


@dataclasses.dataclass
class Summary:
    window_s: float
    steps: int
    busy_s: float                # mean over the chips with events
    span_s: dict                 # harness span -> seconds, all steps
    family_s: dict               # kernel family -> seconds, all chips
    exposed_collective_s: float  # busiest chip, all steps
    ops: list                    # [(op, seconds per chip)], longest first
    gaps: list                   # [(label, seconds)], longest first
    scope_s: dict                # scope path -> seconds per chip, all steps

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def _label(gap, spans, runtime) -> str:
    mid = (gap[0] + gap[1]) / 2
    span = next((n for n, s, e in spans if s <= mid < e), "no span")
    best, overlap = None, 0.0
    for n, s, e in runtime:
        o = min(e, gap[1]) - max(s, gap[0])
        if o > overlap:
            best, overlap = n, o
    return f"{span}|{best}" if best and overlap > 0.5 * (gap[1] - gap[0]) \
        else span


def summarize(data: TraceData, families: dict | None = None) -> Summary:
    from bench import scopes

    families = kernel_families() if families is None else families
    starts = [s for n, s, _ in data.spans if n == "bench.batch"]
    reads = [e for n, _, e in data.spans if n == "bench.read"]
    if not starts or not reads:
        raise RuntimeError("trace holds no complete bench step")
    lo, hi = starts[0], reads[-1]
    window = hi - lo
    spans = [sp for sp in data.spans if lo <= sp[1] < hi]
    span_s = {}
    for n, s, e in spans:
        span_s[n] = span_s.get(n, 0.0) + (e - s) / 1e9
    busy, family_s, ops_s, exposed, gaps = [], {}, {}, [], []
    for evs in data.devices.values():
        evs = [ev for ev in evs if lo <= ev[1] < hi]
        if not evs:
            continue
        union = _union((s, min(e, hi)) for _, s, e, _ in evs)
        busy.append(_length(union))
        compute = _union((s, e) for n, s, e, _ in evs
                         if n not in CONTAINERS and not COLLECTIVE.search(n))
        coll = _union((s, e) for n, s, e, _ in evs if COLLECTIVE.search(n))
        exposed.append((_length(union), _minus(coll, compute)))
        for n, s, e, kern in evs:
            if n in CONTAINERS:
                continue
            ops_s[n] = ops_s.get(n, 0.0) + (e - s) / 1e9
            if kern:
                for fam, keys in families.items():
                    if any(k in n for k in keys):
                        family_s[fam] = family_s.get(fam, 0.0) + (e - s) / 1e9
        edges = [lo] + [x for iv in union for x in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    chips = max(len(busy), 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    return Summary(
        window_s=window / 1e9, steps=len(reads),
        busy_s=sum(busy) / chips / 1e9, span_s=span_s, family_s=family_s,
        exposed_collective_s=max(exposed)[1] / 1e9 if exposed else 0.0,
        ops=sorted(((n, s / chips) for n, s in ops_s.items()),
                   key=lambda x: -x[1]),
        gaps=[(_label(g, spans, data.runtime), (g[1] - g[0]) / 1e9)
              for g in gaps[:10]],
        scope_s=(scopes.scope_seconds(*scopes.load(data.path))
                 if data.path else {}))
