"""model.ssa_blocks_ms: device milliseconds per step of the ops under the
program's ``ssa_blocks`` scope (the blocks of the stage that runs full
spiking self-attention, with their MLPs), forward and backward (the name
stacks of the traced window, ``bench/scopes.py``). Nothing to read where no
op is under it."""
from bench import scopes


def read(run):
    seconds = scopes.under(run.trace.scope_s, "ssa_blocks")
    return None if seconds is None else 1e3 * seconds / run.trace.steps
