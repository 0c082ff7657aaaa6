"""Median over the window's rounds of the host milliseconds a round spends outside
its wait for the device: ``select_s + pack_s + dispatch_s + close_s`` of
``XLASimulator.round_log``, each the duration of the span of that name.  Silent
where the program keeps no such record."""

import statistics

PHASES = ("select_s", "pack_s", "dispatch_s", "close_s")


def read(ctx):
    log = getattr(getattr(ctx.driver, "sim", None), "round_log", None)
    if not log or not ctx.units:
        return None
    return 1000.0 * statistics.median(sum(r[p] for p in PHASES) for r in log[-len(ctx.units):])
