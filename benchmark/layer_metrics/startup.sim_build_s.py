"""Seconds ``XLASimulator.__init__`` took (the span ``sim.build``:
``sim.pack_data``, ``sim.init_variables``, ``sim.build_round_fn`` and the rest), from
the simulator's ``startup_log``.  Silent where the program keeps none."""


def read(ctx):
    log = getattr(getattr(ctx.driver, "sim", None), "startup_log", None)
    return log.get("build_s") if log else None
