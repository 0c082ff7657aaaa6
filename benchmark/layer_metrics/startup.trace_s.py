"""Seconds jax spent tracing Python and lowering to MLIR before the window opened
(``obs.trace_seconds_total()``, from the program's ``jax.monitoring`` listener): what
every start pays again, cache or no cache.  Read after the window, which adds
nothing: the harness counts traces inside it and has found none.  Silent where
the program has no such counter."""

import sys


def read(ctx):
    # the program's obs layer where the cell's driver has loaded and configured it
    obs = sys.modules.get("fedml_tpu.core.obs")
    total = getattr(obs, "trace_seconds_total", None)
    return total() if total is not None and obs.enabled() else None
