"""Milliseconds a round spends in collective instructions on the worst
device (their self time on the device's op line: a TPU core runs one
instruction of that line at a time, so no compute runs beside them).
Silent on one device, where the round has none."""


def read(ctx):
    if ctx.trace is None or ctx.chips < 2 or not ctx.units:
        return None
    per_device = ctx.trace.collective_seconds_by_device()
    if not per_device or max(per_device.values()) <= 0.0:
        return None
    return 1000.0 * max(per_device.values()) / len(ctx.units)
