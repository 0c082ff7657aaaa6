"""Milliseconds a round of a device's idle time that fall under none of the host
phases that prepare, dispatch or close a round: idle under ``round.wait`` (a stall
inside the program) and whatever no span covers.  All of the idle time where the
program writes no such spans.  See ``idle.prep_ms_per_round.py`` and
``benchmark/round_phases.py``."""

from benchmark import round_phases


def read(ctx):
    return round_phases.idle_ms_per_round(ctx, "unattributed")
