"""All tokens trained in whole units (rounds, passes) that ended inside the
window, over the wall time from the window's start to the end of the last of
them, over the cell's chips.  All the work over all the time."""


def read(ctx):
    return ctx.tokens / ctx.window_s / ctx.chips
