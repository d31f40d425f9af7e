"""step_mfu: per cent of the chips' bf16 peak that the step's model FLOPs
reach over the traced window (``step_flops`` of the cell's model family,
the same count whatever implements a site). XLA runs these float32
matmuls at its default precision, one bf16 pass, so the bf16 peak is the
ceiling."""


def read(run):
    t = run.trace
    flops = run.cell.family.step_flops(run.cell.model, run.batch) * t.steps
    return 100.0 * flops / t.window_s / (
        run.chips * run.peaks["bf16_flops_per_s"])
