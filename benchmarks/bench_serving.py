"""Continuous-batching serving benchmark: a seeded Poisson arrival trace.

Replays a deterministic Poisson request-arrival trace (seeded NumPy
generator — same seed, same trace, every run) through the
continuous-batching :class:`ServingEngine` on a reduced spiking
(``cfg.lif``) qwen3 LM and reports:

* throughput — generated tokens/sec and engine steps/sec (wall clock);
* slot occupancy — fraction of slot-steps that served a live request
  (the old wave engine scored ~1/slots here on skewed loads);
* request latency — p50/p99 submit-to-finish, in engine steps and seconds;
* accounting — done / rejected / expired / evicted / faulted counts
  (nothing drops silently); quarantined (``faulted``) requests get one
  clean resubmission, reported as ``requests_retried``. The counters are
  zero in a healthy run — they go live under an injected fault schedule
  (``CHAOS_SCHEDULE``, see docs/RESILIENCE.md).

Emits the same ``metric,value`` CSV blocks as the other benchmarks, so
``benchmarks/run.py`` includes it as the ``serving`` section. Standalone:

    PYTHONPATH=src python benchmarks/bench_serving.py --smoke --json BENCH.json

``--json`` writes a BENCH.json artifact (section ``serving``) in the same
schema as ``run.py``; the CI ``test-serving`` leg uploads it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parent.parent
for _p in (str(_ROOT), str(_ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _build_engine(slots: int, max_seq: int, max_queue: int):
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config, reduced
    from repro.core.lif import LIFConfig
    from repro.models.common import split_tree
    from repro.models.lm import init_lm
    from repro.serving.engine import ServingEngine

    cfg = reduced(get_config("qwen3-0.6b")).replace(lif=LIFConfig())
    params = split_tree(init_lm(jax.random.PRNGKey(0), cfg))[0]
    return ServingEngine(params, cfg, slots=slots, max_seq=max_seq,
                         max_queue=max_queue, cache_dtype=jnp.float32)


def poisson_trace(seed: int, horizon: int, rate: float, max_seq: int):
    """Deterministic arrival trace: {engine_step: [Request, ...]}."""
    import numpy as np
    from repro.serving.scheduler import Request

    rng = np.random.default_rng(seed)
    arrivals: dict[int, list] = {}
    uid = 0
    for t in range(horizon):
        for _ in range(int(rng.poisson(rate))):
            plen = int(rng.integers(2, 9))
            budget = int(rng.integers(4, 25))
            if plen + budget > max_seq:
                budget = max_seq - plen
            arrivals.setdefault(t, []).append(Request(
                uid=uid,
                prompt=[int(x) for x in rng.integers(1, 100, plen)],
                max_new_tokens=budget,
                deadline=(None if rng.random() < 0.8
                          else int(rng.integers(20, 120)))))
            uid += 1
    return arrivals


def run(smoke: bool = False, *, slots: int | None = None,
        rate: float | None = None, horizon: int | None = None,
        seed: int = 0) -> list[str]:
    """Replay the trace; returns ``metric,value`` CSV lines."""
    import numpy as np

    slots = slots or (4 if smoke else 8)
    horizon = horizon or (40 if smoke else 400)
    rate = rate if rate is not None else (0.3 if smoke else 0.5)
    max_seq = 64 if smoke else 256
    arrivals = poisson_trace(seed, horizon, rate, max_seq)
    engine = _build_engine(slots, max_seq, max_queue=4 * slots)

    # Warm the single trace outside the timed region.
    t0 = time.perf_counter()
    engine.step()
    compile_s = time.perf_counter() - t0

    n_submitted = 0
    t0 = time.perf_counter()
    while engine.step_count < horizon or engine.sched.has_work():
        for req in arrivals.get(engine.step_count, []):
            engine.submit(req)
            n_submitted += 1
        engine.step()
        if engine.step_count > horizon + 100_000:   # pragma: no cover
            raise RuntimeError("serving bench failed to drain")
    wall = time.perf_counter() - t0

    # One retry round for quarantined requests: a numeric fault is
    # slot-local (the engine flushed the slot), so a clean resubmission
    # of the same prompt is expected to finish.
    retried = 0
    if engine.faulted:
        from repro.serving.scheduler import Request
        for bad in list(engine.faulted):
            if engine.submit(Request(uid=1_000_000 + bad.uid,
                                     prompt=list(bad.prompt),
                                     max_new_tokens=bad.max_new_tokens)):
                retried += 1
            n_submitted += 1
        while engine.sched.has_work():
            engine.step()

    lat = [r.latency_steps for r in engine.finished]
    p50, p99 = (np.percentile(lat, [50, 99]) if lat else (0.0, 0.0))
    sec_per_step = wall / max(1, engine.step_count)
    done = len(engine.finished)
    assert done + len(engine.rejected) + len(engine.expired) + \
        len(engine.evicted) + len(engine.faulted) == n_submitted, \
        "serving accounting broke: a request was dropped silently"
    return [
        "metric,value",
        f"slots,{slots}",
        f"trace_horizon_steps,{horizon}",
        f"poisson_rate,{rate}",
        f"requests_submitted,{n_submitted}",
        f"requests_done,{done}",
        f"requests_rejected,{len(engine.rejected)}",
        f"requests_expired,{len(engine.expired)}",
        f"requests_evicted,{len(engine.evicted)}",
        f"requests_faulted,{len(engine.faulted)}",
        f"requests_retried,{retried}",
        f"tokens_generated,{engine.generated_tokens}",
        f"engine_steps,{engine.step_count}",
        f"compile_seconds,{compile_s:.3f}",
        f"wall_seconds,{wall:.3f}",
        f"tokens_per_sec,{engine.generated_tokens / max(wall, 1e-9):.1f}",
        f"steps_per_sec,{engine.step_count / max(wall, 1e-9):.1f}",
        f"slot_occupancy,{engine.occupancy:.3f}",
        f"p50_latency_steps,{float(p50):.1f}",
        f"p99_latency_steps,{float(p99):.1f}",
        f"p50_latency_s,{float(p50) * sec_per_step:.4f}",
        f"p99_latency_s,{float(p99) * sec_per_step:.4f}",
        f"decode_traces,{engine.trace_count()}",
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace (40 steps, 4 slots)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write a BENCH.json artifact (section 'serving')")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--horizon", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    # Explicit opt-in fault injection (same contract as launch.train):
    # CHAOS_SCHEDULE activates a seeded schedule, nothing else does.
    from repro.chaos.inject import activate_from_env
    injector = activate_from_env()

    t0 = time.perf_counter()
    lines = run(smoke=args.smoke, slots=args.slots, rate=args.rate,
                horizon=args.horizon, seed=args.seed)
    dt = time.perf_counter() - t0
    print(f"== serving ({dt:.1f}s) ==")
    print("\n".join(lines))
    if injector is not None:
        for event in injector.events:
            print(f"chaos_event,{event}")
    if args.json:
        from benchmarks.run import parse_section
        section = parse_section(lines)
        section["_section_seconds"] = round(dt, 2)
        report = {"smoke": args.smoke, "generated_unix": int(time.time()),
                  "sections": {"serving": section}}
        Path(args.json).write_text(json.dumps(report, indent=1,
                                              sort_keys=True))
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
