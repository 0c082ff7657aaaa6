"""Device milliseconds a round spends in the block-diffusion attention mixers
(``lm.attn.bd``: the projections, the q/k norms, the rotation, the ``bd_flash_*`` kernels
and the output projection), forward, recomputed forward and backward: self time of the
trace's op events whose instruction the compiled round names under that scope
(``benchmark/scope_times.py``), mean over the cell's devices.  Silent without a trace,
where the driver kept no shapes of the round program, or where the program has no such
scope."""

from benchmark import scope_times


def read(ctx):
    return scope_times.device_ms_per_round(ctx, "lm.attn.bd")
