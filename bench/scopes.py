"""Device time by the program's named scopes, from a ``--trace 1`` run's
profiler trace.

Each device op of a TPU trace carries the JAX name stack of the code that
built it (``jit(train_step)/transpose(jvp(blocks))/while/body/closed_call/
pssa.qkv/dot_general``): the ``tf_op`` stat of the op's event metadata.
The profiler API (``jax.profiler.ProfileData``) exposes event stats but
not metadata stats, so :func:`name_stacks` reads them from the file's
protobuf wire format. :func:`scope_path` keeps the named scopes of a stack
(``blocks/pssa.qkv``), and :func:`scope_seconds` sums device time by that
path over the traced window that ``trace.summarize`` uses, per chip.

``trace.summarize`` carries the result as ``Summary.scope_s``, which the
per-layer readers of scopes read.

A persistent compilation cache keyed without debug information (JAX's
default) can hand a program an executable compiled from the same
computation before its scopes existed; its ops then carry the old name
stacks. So ``bench/run.py`` sets
``jax_compilation_cache_include_metadata_in_key`` in a ``--trace 1`` run,
and only there: untraced runs keep their cache keys.
"""
from __future__ import annotations

import re

from bench.trace import CONTAINERS, op_name

#: Name-stack parts JAX adds that name no scope: control flow and calls.
STRUCTURAL = re.compile(
    r"^(while|body|cond|branch_\d+_fun|closed_call|checkpoint|"
    r"rematted_computation)$")
#: A transformation around a scope (``transpose(jvp(blocks))``) or a
#: function (``jit(lif_scan)``).
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_SCOPE = re.compile(r"^[A-Za-z_][\w.\-]*$")


def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes, lo: int, hi: int):
    """(field number, value) of one protobuf message in ``b[lo:hi]``; a
    length-delimited value is its (start, end) in ``b``."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = None, i + 8
        elif kind == 5:
            v, i = None, i + 4
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(b: bytes, at) -> str:
    return b[at[0]:at[1]].decode("utf-8", "replace")


def name_stacks(path: str) -> dict[str, dict[str, str]]:
    """plane name -> {event metadata name: its ``tf_op`` stat}, for the
    ``/device:TPU:<n>`` planes of an ``.xplane.pb``.

    The metadata name is the instruction text an ``XLA Ops`` event is
    named by. Read from the wire format of tsl's ``XSpace`` (planes = 1;
    in ``XPlane`` name = 2, event_metadata = 4, stat_metadata = 5; in
    ``XEventMetadata`` name = 2, stats = 5; in ``XStat`` metadata_id = 1,
    str_value = 5, ref_value = 7), skipping the event lines."""
    with open(path, "rb") as f:
        b = f.read()
    out = {}
    for field, plane in _fields(b, 0, len(b)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(b, *plane):
            if f == 2:
                name = _text(b, v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                for k, entry in _fields(b, *v):
                    if k == 2:
                        meta = dict(_fields(b, *entry))
                        stat_names[meta.get(1, 0)] = (
                            _text(b, meta[2]) if 2 in meta else "")
        if not name.startswith("/device:TPU:"):
            continue
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        table = out[name] = {}
        for entry in events:
            for k, meta in _fields(b, *entry):
                if k != 2:
                    continue
                op, stack = "", ""
                for f, v in _fields(b, *meta):
                    if f == 2:
                        op = _text(b, v)
                    elif f == 5:
                        stat = dict(_fields(b, *v))
                        if stat.get(1) in tf_op:
                            if 5 in stat:
                                stack = _text(b, stat[5])
                            elif 7 in stat:
                                stack = stat_names.get(stat[7], "")
                table[op] = stack
    return out


def scope_path(stack: str) -> str:
    """The named scopes of a JAX name stack, outermost first:
    ``jit(train_step)/transpose(jvp(blocks))/while/body/closed_call/
    pssa.qkv/dot_general:`` -> ``blocks/pssa.qkv``. Transformations are
    unwrapped, and function names (``jit(...)``), control flow, einsum
    specs and the op itself (the last part) dropped; where XLA merged two
    locations (``transpose;attn_av``) the last one is kept, and a scope
    repeated by a nested call (``blocks/blocks/pssa.lif``) counts once.
    "" for an op under no scope."""
    if ":" in stack:
        stack = stack.rsplit(":", 1)[0]     # the profiler's ":<type>"
    kept = []
    for part in stack.split("/")[:-1]:
        part = part.split(";")[-1]
        while (m := _WRAPPED.match(part)) and m.group(1) not in ("jit",
                                                                 "pjit"):
            part = m.group(2)
        if (_SCOPE.match(part) and not STRUCTURAL.match(part)
                and kept[-1:] != [part]):
            kept.append(part)
    return "/".join(kept)


def load(path: str) -> tuple[dict, tuple[int, int]]:
    """(plane name -> [(op, name stack, start_ns, end_ns)] of its ``XLA
    Ops`` line, (window start, window end)): the window runs from the
    first ``bench.batch`` to the last ``bench.read``, as in
    ``trace.summarize``."""
    from jax.profiler import ProfileData

    stacks = name_stacks(path)
    devices, starts, reads = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            known = stacks.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (op_name(e.name), known.get(e.name, ""), e.start_ns,
                         e.end_ns) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.batch":
                        starts.append(e.start_ns)
                    elif e.name == "bench.read":
                        reads.append(e.end_ns)
    if not starts or not reads:
        raise RuntimeError("trace holds no complete bench step")
    return devices, (min(starts), max(reads))


def scope_seconds(devices: dict, window: tuple[int, int]) -> dict:
    """scope path -> device seconds per chip of the non-container ops that
    start inside ``window`` (``load``'s two parts)."""
    lo, hi = window
    total, chips = {}, 0
    for evs in devices.values():
        inside = False
        for op, stack, s, e in evs:
            if not lo <= s < hi:
                continue
            inside = True
            if op in CONTAINERS:
                continue
            path = scope_path(stack)
            total[path] = total.get(path, 0.0) + (e - s) / 1e9
        chips += inside
    return {k: v / max(chips, 1) for k, v in total.items()}


def under(scope_s: dict, scope: str, exclude=()) -> float | None:
    """Seconds of ``scope_s`` under ``scope`` and under none of
    ``exclude``; None where no op is under ``scope``."""
    hits = [(set(path.split("/")), v) for path, v in scope_s.items()]
    hits = [(parts, v) for parts, v in hits if scope in parts]
    if not hits:
        return None
    return sum(v for parts, v in hits if not set(exclude) & parts)
