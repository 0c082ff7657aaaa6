"""Share of the traced window in which no operation ran on the device:
1 - union of the device-op intervals / window, mean over the cell's devices."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0.0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.window_s)
