"""The whole step's share of the chip's bf16 peak for training the ``nemotron_h``
decoder: operations the forward and backward passes require for the tokens trained in
the window (``benchmark/flops_nemotron_h.py``: every weight a token meets, the expected
held experts, the causal half-square of the attention layer, the SSD scans at the
published chunk; recomputation not counted) over window seconds x chips x the published
peak.  Everything the window spends is in the denominator."""

from benchmark import flops_nemotron_h


def read(ctx):
    if not ctx.sequences or "hybrid_override_pattern" not in ctx.model:
        return None
    need = flops_nemotron_h.train_flops(ctx.model, ctx.sequences,
                                        int(ctx.traffic["sequence_length"]))
    return 100.0 * need / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
