"""Device milliseconds a round spends in the step's loss (``fed.loss`` in ``ml/engine/train.py``: the float32 softmax cross-entropy
over [rows, vocabulary], the sum of what the module sowed into ``losses``, and their cotangent):
self time of the trace's op events whose instruction the program's own table (``XLASimulator.round_scopes()``) names
under that scope, mean over the cell's devices (``benchmark/program_scopes.py``).  Silent without a trace and on a
program that hands out no table."""

from benchmark import program_scopes


def read(ctx):
    return program_scopes.device_ms_per_round(ctx, "fed.loss")
