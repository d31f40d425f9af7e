"""host.batch_ms: host milliseconds per step spent making the step's batch
(``bench.batch``) and placing it on the mesh (``bench.place``), from the
harness's spans in the trace. Moves the throughput while the device
waits for the host."""


def read(run):
    s = run.trace.span_s
    return 1e3 * (s.get("bench.batch", 0.0) + s.get("bench.place", 0.0)) \
        / run.trace.steps
