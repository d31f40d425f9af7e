"""lif_roofline: per cent of the LIF kernels' device time (kernels/lif_soma.py,
found by name in the trace) that their roofline needs: the LIF work the
plan gives them (``kernel_work`` of the cell's model family, 20 bytes per
neuron-step, bound by memory) over HBM bandwidth. Nothing to read where no
LIF kernel ran."""

from bench import work


def read(run):
    seconds = run.trace.family_s.get("lif")
    pieces = run.cell.family.kernel_work("lif", run.cell.model, run.batch,
                                         run.plan)
    if not seconds or not pieces:
        return None
    least = work.roofline_seconds(pieces, run.peaks) * run.trace.steps
    return 100.0 * least / seconds
