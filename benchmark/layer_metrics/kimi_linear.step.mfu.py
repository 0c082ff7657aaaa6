"""The whole step's share of the chip's bf16 peak for the ``kimi_linear``
decoder: operations the forward and backward passes require for the tokens
trained in the window (``benchmark/flops_kimi_linear.py``: expected expert
assignments, the KDA recurrence as written, MLA's causal half-square;
recomputation not counted) over window seconds x chips x the published peak.
Everything the window spends is in the denominator."""

from benchmark import flops_kimi_linear


def read(ctx):
    if not ctx.sequences or "linear_attn_config" not in ctx.model:
        return None
    need = flops_kimi_linear.train_flops(
        ctx.model, ctx.sequences, int(ctx.traffic["sequence_length"]))
    return 100.0 * need / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
