"""Device milliseconds a round spends in the KDA mixers (``lm.kda``: projections, short convolutions, gates, the chunkwise delta rule, output norm and projection), forward, recomputed forward and
backward: self time of the trace's op events whose instruction the compiled
round names under that scope (``benchmark/scope_times.py``), mean over the
cell's devices.  Silent without a trace or where the driver kept no shapes of
the round program."""

from benchmark import scope_times


def read(ctx):
    return scope_times.device_ms_per_round(ctx, "lm.kda")
