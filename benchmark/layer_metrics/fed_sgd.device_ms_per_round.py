"""Device milliseconds a round spends in the client optimizer's update (``fed.sgd`` in ``ml/engine/packed.py``: ``tx.update`` and
``optax.apply_updates``, the read-modify-write of the float32 parameters), every local step:
self time of the trace's op events whose instruction the program's own table (``XLASimulator.round_scopes()``) names
under that scope, mean over the cell's devices (``benchmark/program_scopes.py``).  Silent without a trace and on a
program that hands out no table."""

from benchmark import program_scopes


def read(ctx):
    return program_scopes.device_ms_per_round(ctx, "fed.sgd")
