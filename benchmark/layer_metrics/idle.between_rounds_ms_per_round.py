"""Milliseconds a round of a device's idle time lying between the end of a
``round.wait`` span and the start of the next ``round.select``: ``round.close``,
``train()``'s tail and preamble, and the caller.  See ``idle.prep_ms_per_round.py``
and ``benchmark/round_phases.py``."""

from benchmark import round_phases


def read(ctx):
    return round_phases.idle_ms_per_round(ctx, "between_rounds")
