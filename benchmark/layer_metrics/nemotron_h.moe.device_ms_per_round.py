"""Device milliseconds a round spends in the ``nemotron_h`` expert layers (``lm.moe.*``:
the router, dispatch, the grouped products of the squared-ReLU experts, combine, the
shared expert), forward, recomputed forward and backward; ``moe.device_ms_per_round``'s
reading (``benchmark/scope_times.py``) in this configuration's cell."""

from benchmark import scope_times


def read(ctx):
    return scope_times.device_ms_per_round(ctx, "lm.moe.")
