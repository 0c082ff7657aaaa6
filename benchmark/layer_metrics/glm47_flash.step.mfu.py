"""The whole step's share of the chip's bf16 peak for the ``glm4_moe_lite`` decoder:
operations the forward and backward passes require for the tokens trained in the
window (``benchmark/flops_glm47_flash.py``: expected expert assignments, six causal
half-squares at 256 / 256 and 20 heads — five layers and the prediction module's block —
and two heads; recomputation not counted) over window seconds x chips x the published
peak.  Everything the window spends is in the denominator."""

from benchmark import flops_glm47_flash


def read(ctx):
    if not ctx.sequences or ctx.model.get("model_type") != "glm4_moe_lite":
        return None
    need = flops_glm47_flash.train_flops(
        ctx.model, ctx.sequences, int(ctx.traffic["sequence_length"]))
    return 100.0 * need / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
