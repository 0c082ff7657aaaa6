"""Milliseconds a round of a device's idle time (the window less the union of its
op intervals, mean over the cell's devices) lying under the host spans
``round.select`` and ``round.pack``: cohort selection, the schedule, ``pack_round``
and its uploads.  Cut from one split with the other ``idle.*`` metrics
(``benchmark/round_phases.py``), so the four add up to the window's idle.  Reads 0
where the program has no such spans: the idle is then all unattributed."""

from benchmark import round_phases


def read(ctx):
    return round_phases.idle_ms_per_round(ctx, "prep")
