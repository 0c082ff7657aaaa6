"""Device milliseconds a round spends in the dense feed-forward layers with their norm and residual add (``lm.mlp``: every layer of
``TransformerLM``, the leading dense layer of ``kimi_linear`` and ``glm4_moe_lite``), forward, recomputed forward and backward:
self time of the trace's op events whose instruction the program's own table (``XLASimulator.round_scopes()``) names
under that scope, mean over the cell's devices (``benchmark/program_scopes.py``).  Silent without a trace and on a
program that hands out no table."""

from benchmark import program_scopes


def read(ctx):
    return program_scopes.device_ms_per_round(ctx, "lm.mlp")
