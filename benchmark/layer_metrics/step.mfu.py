"""The whole step's share of the chip's bf16 peak: operations the forward and
backward passes require for the tokens trained in the window
(``benchmark/flops.py``; recomputation not counted) over window seconds x
chips x the device kind's published peak.  Everything the window spends —
hand-off, flushes, host gaps — is in the denominator."""


def read(ctx):
    if not ctx.sequences:
        return None
    need = ctx.flops.train_flops(ctx.model, ctx.sequences, int(ctx.traffic["sequence_length"]))
    return 100.0 * need / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
