"""How many programs the XLA backend compiled before the window opened
(``obs.compiles_total()``: compile requests less persistent-cache hits).  On a warm
cache it is the programs under jax's cache thresholds, which compile on every
start, and it repeats exactly.  Read after the window, which adds none (the
harness counts them).  Silent where the program has no such counter."""

import sys


def read(ctx):
    # the program's obs layer where the cell's driver has loaded and configured it
    obs = sys.modules.get("fedml_tpu.core.obs")
    total = getattr(obs, "compiles_total", None)
    return float(total()) if total is not None and obs.enabled() else None
