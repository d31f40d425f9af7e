"""model.embed_ms: device milliseconds per step of the ops under the
program's ``embed`` scope (every patch embedding of a hierarchical model:
its convolutions, BatchNorms, max-pools, shortcuts and LIFs), forward and
backward (the name stacks of the traced window, ``bench/scopes.py``).
Nothing to read where no op is under it."""
from bench import scopes


def read(run):
    seconds = scopes.under(run.trace.scope_s, "embed")
    return None if seconds is None else 1e3 * seconds / run.trace.steps
