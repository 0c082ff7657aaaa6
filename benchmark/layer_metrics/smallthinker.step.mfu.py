"""The whole step's share of the chip's bf16 peak for the SmallThinker decoder:
operations the forward and backward passes require for the tokens trained in the
window (``benchmark/flops_smallthinker.py``: expected expert assignments, the
band's pairs of a windowed layer and the causal half-square of a global one;
recomputation not counted) over window seconds x chips x the published peak.
Everything the window spends is in the denominator."""

from benchmark import flops_smallthinker


def read(ctx):
    if not ctx.sequences or "sliding_window_layout" not in ctx.model:
        return None
    need = flops_smallthinker.train_flops(
        ctx.model, ctx.sequences, int(ctx.traffic["sequence_length"]))
    return 100.0 * need / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
