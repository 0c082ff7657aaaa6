"""Device milliseconds a round spends in the ``glm4_moe_lite`` expert layers (``lm.moe.*``:
route, dispatch, the grouped products, combine, the shared expert), forward, recomputed
forward and backward; ``moe.device_ms_per_round``'s reading (``benchmark/scope_times.py``) in
this configuration's cell.  The prediction module's expert layer counts here too (its
scopes nest inside ``lm.mtp``)."""

from benchmark import scope_times


def read(ctx):
    return scope_times.device_ms_per_round(ctx, "lm.moe.")
