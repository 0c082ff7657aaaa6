"""Device milliseconds a round spends in the multi-token-prediction module (``lm.mtp``:
``lm.mtp.merge`` — two norms, the next token's embedding, ``W_eh`` —, its whole block,
whose ``lm.mla`` and ``lm.moe.*`` scopes nest inside ``lm.mtp`` and so count here AND in
``glm47_flash.mla.`` / ``glm47_flash.moe.device_ms_per_round``, and ``lm.mtp.head`` — norm,
the shared head, its loss), forward, recomputed forward and backward: self time of the
trace's op events whose instruction the compiled round names under that scope
(``benchmark/scope_times.py``), mean over the cell's devices.  The module's grouped
products are not in it: XLA's ``ragged-dot`` kernels carry their kernel's name in place
of jax's name stack, and ``scope_times`` files every one under ``lm.moe.experts``.  Silent
without a trace, where the driver kept no shapes of the round program, or where the
program has no such scope."""

from benchmark import scope_times


def read(ctx):
    return scope_times.device_ms_per_round(ctx, "lm.mtp")
