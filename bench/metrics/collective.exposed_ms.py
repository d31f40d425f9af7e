"""collective.exposed_ms: milliseconds per step, on the busiest chip, in
which a collective (all-reduce, all-gather, reduce-scatter, permute) ran
and no computation did. Nothing to read on one chip."""


def read(run):
    if run.chips < 2:
        return None
    return 1e3 * run.trace.exposed_collective_s / run.trace.steps
