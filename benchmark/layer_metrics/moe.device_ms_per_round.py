"""Device milliseconds a round spends in the expert layers (``lm.moe.*``: route, dispatch, experts, combine, shared), forward, recomputed forward and
backward: self time of the trace's op events whose instruction the compiled
round names under that scope (``benchmark/scope_times.py``), mean over the
cell's devices.  Silent without a trace or where the driver kept no shapes of
the round program."""

from benchmark import scope_times


def read(ctx):
    return scope_times.device_ms_per_round(ctx, "lm.moe.")
