"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

Nothing here depends on the model: what does comes from the cell's model
family (``bench/families/<family>.py``, found by ``cells.find_cell``). The
window drives the program's own pure train step, as the family builds it,
on a ``make_test_mesh`` mesh: ``jax.jit(step, donate_argnums=(0, 1, 2))``,
each batch from the family's generator placed by ``place_batch``, and the
loss and the non-finite flag read on the host after every step. Set-up
compiles that step, drives it through the first steps that the comparison
reads, then warms it; the window then runs the same compiled step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import compare
from bench.cells import Cell, metric_reader
from bench.weights import make_params

#: Steps after the first ones and before the window (same shapes).
WARM_STEPS = 2
#: Seconds of the window a ``--trace 1`` run records.
TRACE_SECONDS = 3.0
GIB = 2**30


class Compiles:
    """Counts executables JAX builds (compiled or read from the persistent
    cache) while installed, and the seconds spent on them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


def _span(name: str, tracing: bool):
    """A harness span (``bench.batch`` ...) in the profiler's trace while it
    records; nothing otherwise."""
    import jax
    return (jax.profiler.TraceAnnotation(name) if tracing
            else contextlib.nullcontext())


@dataclasses.dataclass
class Run:
    """What a per-layer reader may read (``bench/metrics/<name>.py``)."""
    cell: Cell
    batch: int
    chips: int
    peaks: dict
    plan: dict              # site -> implementation that runs it
    trace: object           # bench.trace.Summary of the traced steps


def planned_bytes(compiled) -> int:
    """Bytes the compiler plans for one call on the fullest device:
    arguments + outputs - aliases + temporaries."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def _structs(tree):
    import jax
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding), tree)


def _keep_shardings(fn, like):
    """``fn`` with its state outputs placed as ``like``'s leaves are, so
    that a hooked step can be called again on what it returns."""
    import jax
    shardings = jax.tree.map(lambda a: a.sharding, like)

    def step(*args):
        *state, metrics = fn(*args)
        return (*jax.lax.with_sharding_constraint(tuple(state), shardings),
                metrics)
    return step


def reference_readings(cell: Cell, seed: int, structs, device, traffic):
    """The reference's readings of the first steps, on ``device``, at the
    configuration's precision. ``structs`` are the program's (params,
    state) shapes; the reference's optimizer state is AdamW's moments and
    step count."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    family = cell.family
    on = SingleDeviceSharding(device)
    params_s, state_s = (jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on), t)
        for t in structs)
    start = lambda: make_params(params_s, seed, family.init)  # noqa: E731
    params = start()
    state = family.reference_state(state_s)
    opt = {"m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params),
           "step": jax.device_put(jnp.zeros((), jnp.int32), on)}
    batches = [traffic.inputs({k: jax.device_put(v, on)
                               for k, v in traffic.batch(i).items()})
               for i in range(compare.FIRST_STEPS)]
    readings, _ = compare.first_steps(
        jax.jit(family.reference_step(cell.model),
                donate_argnums=(0, 1, 2)),
        params, state, opt, batches, cell, start)
    return readings


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        peaks: dict, t0: float, step_hook=None, log=sys.stderr) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line's
    object. ``step_hook`` (tests and the control's readings only) wraps
    the program's pure step before it is jitted."""
    import jax

    from repro.launch.mesh import make_test_mesh, use_mesh
    from repro.train.data import place_batch

    family, mix, batch = cell.family, cell.mix, int(cell.mix["batch"])
    mesh = make_test_mesh(mix["mesh"]["data"], mix["mesh"]["model"],
                          devices=devices)
    traffic = family.traffic(mix, cell.model, seed)
    compiles = Compiles()

    def place(host):
        return traffic.inputs(place_batch(host, mesh))

    with compiles, use_mesh(mesh):
        (params, state, opt), fn, plan = family.program(cell, mesh)
        struct, state_struct = _structs(params), _structs(state)
        params = make_params(struct, seed, family.init)
        if step_hook is not None:
            fn = _keep_shardings(step_hook(fn, mesh), (params, state, opt))
        step = jax.jit(fn, donate_argnums=(0, 1, 2))
        firsts = [place(traffic.batch(i)) for i in range(compare.FIRST_STEPS)]
        compiled = step.lower(params, state, opt, *firsts[0]).compile()
        hbm = planned_bytes(compiled)
        got, (params, state, opt) = compare.first_steps(
            compiled, params, state, opt, firsts, cell,
            lambda: make_params(struct, seed, family.init))
        del firsts
        n = compare.FIRST_STEPS
        for i in range(n, n + WARM_STEPS):
            params, state, opt, m = compiled(params, state, opt,
                                             *place(traffic.batch(i)))
            float(m["loss"])
        setup_compiles, setup_compile_s = compiles.count, compiles.seconds
        setup_s = time.perf_counter() - t0

        trace_dir, tracing = None, trace
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(trace_dir)
        durations, nonfinite, losses_finite = [], 0, True
        step_no = n + WARM_STEPS
        start = time.perf_counter()
        while True:
            a = time.perf_counter()
            with _span("bench.batch", tracing):
                host = traffic.batch(step_no)
            with _span("bench.place", tracing):
                inputs = place(host)
            with _span("bench.dispatch", tracing):
                params, state, opt, m = compiled(params, state, opt,
                                                 *inputs)
            with _span("bench.read", tracing):
                loss, nf = float(m["loss"]), float(m["nonfinite"])
            b = time.perf_counter()
            durations.append(b - a)
            nonfinite += nf > 0
            losses_finite &= math.isfinite(loss)
            step_no += 1
            if tracing and b - start >= min(TRACE_SECONDS, seconds):
                jax.profiler.stop_trace()
                tracing = False
            if b - start >= seconds:
                break
        end = time.perf_counter()
        window_compiles = compiles.count - setup_compiles

    used = list(devices)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    del params, state, opt, m, compiled, step
    want = reference_readings(cell, seed, (struct, state_struct), used[0],
                              traffic)
    numbers = compare.gaps(got, want)
    correct = (compare.judge(numbers, cell.limits) and nonfinite == 0
               and losses_finite)
    print(f"[bench] losses {got['losses']} reference {want['losses']}",
          file=log)
    print(f"[bench] set-up {setup_s:.3f} s, {setup_compiles} executables "
          f"({setup_compile_s:.3f} s); window {len(durations)} steps, "
          f"{window_compiles} executables built in it", file=log)

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(durations),
           "failed": int(nonfinite)}
    if trace:
        from bench import trace as tr
        summary = tr.summarize(tr.load_dir(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        record = Run(cell=cell, batch=batch, chips=len(used), peaks=peaks,
                     plan=plan, trace=summary)
        metrics = {}
        for m_ in cell.per_layer:
            value = metric_reader(m_["name"])(record)
            if value is not None:
                metrics[m_["name"]] = {"value": value, "unit": m_["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out.update(metrics=metrics, device=device,
                   breakdown=summary.breakdown())
    else:
        e2e = {
            family.THROUGHPUT:
                len(durations) * traffic.samples / (end - start),
            "step_ms_p95": 1e3 * float(np.percentile(durations, 95)),
            "step_hbm_gib": hbm / GIB,
            "setup_s": setup_s,
        }
        out.update(metrics={m_["name"]: {"value": e2e[m_["name"]],
                                         "unit": m_["unit"]}
                            for m_ in cell.end_to_end},
                   device=device)
    out["compared"] = {k: {"value": numbers[k], "limit": cell.limits[k]}
                       for k in compare.NUMBERS
                       if k in cell.limits and k in numbers}
    return out


def report(out: dict, log=sys.stderr) -> None:
    """The numbers compared, as the last lines of standard error."""
    for k, v in out["compared"].items():
        print(f"compared {k} {v['value']!r} limit {v['limit']!r}", file=log)
    log.flush()
