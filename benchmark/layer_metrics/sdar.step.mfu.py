"""The whole step's share of the chip's bf16 peak for block-diffusion training of the
``sdar_moe`` decoder: operations the forward and backward passes require for the DATA
tokens trained in the window (``benchmark/flops_sdar.py``: both copies through every
layer but the last, whose clean half needs only k and v; the block-diffusion pairs; the
head over the noised half; recomputation not counted) over window seconds x chips x the
published peak.  Everything the window spends is in the denominator."""

from benchmark import flops_sdar


def read(ctx):
    if not ctx.sequences or "block_length" not in ctx.model:
        return None
    need = flops_sdar.train_flops(ctx.model, ctx.sequences, int(ctx.traffic["sequence_length"]))
    return 100.0 * need / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
