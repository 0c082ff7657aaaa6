"""Median wall seconds of a round in the window, as the simulator times it
(``XLASimulator.round_times``: host clock after ``block_until_ready`` of the
new global model)."""

import statistics


def read(ctx):
    if ctx.traffic["driver"] != "sim" or not ctx.units:
        return None
    return statistics.median(u["program_seconds"] for u in ctx.units)
