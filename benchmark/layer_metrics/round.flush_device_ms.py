"""Device milliseconds a round spends in the client-boundary branch of the packed
stream (``fed.flush`` in ``ml/engine/packed.py``): the ``conditional`` events inside
the round module's ``while``, whole duration, both branches, mean over the cell's
devices.  Told by structure (``benchmark/round_phases.py``); fails the run where the
simulator is packed and no such event is in the trace."""

from benchmark import round_phases


def read(ctx):
    return round_phases.device_ms_per_round(ctx, "flush_s", "flush")
