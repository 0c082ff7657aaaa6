"""Device milliseconds a round spends in the latent-attention mixers of the
``glm4_moe_lite`` decoder (``lm.mla``: the low-rank query's two projections and norm, the
latent's projections and norm, the rotation under ``lm.mla.rope``, the flash kernels at
256 / 256, the output projection), forward, recomputed forward and backward:
``mla.device_ms_per_round``'s reading (``benchmark/scope_times.py``) in this
configuration's cell.  The prediction module's block counts here too: its mixer's scope
nests inside ``lm.mtp``, and this reader matches ``lm.mla`` wherever it stands."""

from benchmark import scope_times


def read(ctx):
    return scope_times.device_ms_per_round(ctx, "lm.mla")
