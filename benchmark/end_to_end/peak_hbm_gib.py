"""The fullest device's ``peak_bytes_in_use``, read once the window has closed
and before the reference touches the chip."""


def read(ctx):
    return ctx.device["memory_peak_bytes"] / 1024**3
