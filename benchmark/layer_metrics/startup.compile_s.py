"""Seconds the XLA backend spent compiling before the window opened, from the
program's own counter (``obs.compile_seconds_total()``, a jax.monitoring
listener).  A run that finds every program in the cache reads what is left:
programs under jax's one-second cache threshold."""


def read(ctx):
    return ctx.setup["compile_s"]
