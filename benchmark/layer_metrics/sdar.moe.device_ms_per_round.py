"""Device milliseconds a round spends in the ``sdar_moe`` expert layers (``lm.moe.*``:
the router on the layer's own input, dispatch, the grouped products, combine), forward,
recomputed forward and backward; ``moe.device_ms_per_round``'s reading
(``benchmark/scope_times.py``) in this configuration's cell."""

from benchmark import scope_times


def read(ctx):
    return scope_times.device_ms_per_round(ctx, "lm.moe.")
