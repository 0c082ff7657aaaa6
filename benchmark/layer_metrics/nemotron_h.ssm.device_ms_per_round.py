"""Device milliseconds a round spends in the Mamba-2 mixers (``lm.ssm``: the input
projection, the short convolution, the gates, the SSD scan, the gated norm and the
output projection), forward, recomputed forward and backward: self time of the trace's
op events whose instruction the compiled round names under that scope
(``benchmark/scope_times.py``), mean over the cell's devices.  Silent without a trace,
where the driver kept no shapes of the round program, or where the program has no such
scope."""

from benchmark import scope_times


def read(ctx):
    return scope_times.device_ms_per_round(ctx, "lm.ssm")
