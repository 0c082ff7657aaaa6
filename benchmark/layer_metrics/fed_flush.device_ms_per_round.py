"""Device milliseconds a round spends in the client boundary of the packed stream (``fed.flush`` in ``ml/engine/packed.py``): ``acc += w·params``, the
algorithm's contribution and the per-slot output, once a client.  Takes the place of ``round.flush_device_ms``, which
told the boundary by a ``conditional`` the stream no longer has:
self time of the trace's op events whose instruction the program's own table (``XLASimulator.round_scopes()``) names
under that scope, mean over the cell's devices (``benchmark/program_scopes.py``).  Silent without a trace and on a
program that hands out no table."""

from benchmark import program_scopes


def read(ctx):
    return program_scopes.device_ms_per_round(ctx, "fed.flush")
