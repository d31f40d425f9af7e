"""device.idle_share: per cent of the traced window in which no operation
ran on the device (1 - union of op intervals / window), mean over chips."""


def read(run):
    t = run.trace
    return 100.0 * (1.0 - t.busy_s / t.window_s)
