"""Milliseconds a round of a device's idle time lying under the host span
``round.dispatch``: the call of the round program until it returns.  See
``idle.prep_ms_per_round.py`` and ``benchmark/round_phases.py``."""

from benchmark import round_phases


def read(ctx):
    return round_phases.idle_ms_per_round(ctx, "dispatch")
