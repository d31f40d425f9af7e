"""spike_mm_roofline: per cent of the packed spike-matmul kernels' device time
(kernels/spike_matmul.py and the patch and attention products of
conv_spike.py and the PSSA, found by name in the trace) that their
roofline needs: per product the larger of its FLOPs over the bf16 peak and
its bytes ({0,1} operands at 1 bit) over HBM bandwidth (``kernel_work`` of
the cell's model family). Nothing to read where no such kernel ran."""

from bench import work


def read(run):
    seconds = run.trace.family_s.get("spike_mm")
    pieces = run.cell.family.kernel_work("spike_mm", run.cell.model,
                                         run.batch, run.plan)
    if not seconds or not pieces:
        return None
    least = work.roofline_seconds(pieces, run.peaks) * run.trace.steps
    return 100.0 * least / seconds
