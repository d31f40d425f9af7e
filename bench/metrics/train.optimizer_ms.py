"""train.optimizer_ms: device milliseconds per step of the ops under the
program's ``optimizer`` scope (the update, the finite check and the
select), from the name stacks of the traced window (``bench/scopes.py``).
Nothing to read where no op is under it."""
from bench import scopes


def read(run):
    seconds = scopes.under(run.trace.scope_s, "optimizer")
    return None if seconds is None else 1e3 * seconds / run.trace.steps
