"""The flash forward kernel's share of its roofline at latent attention's 256 / 256 (q
and k of ``qk_nope_head_dim + qk_rope_head_dim``, v of ``v_head_dim``, 20 heads): least
time the chip could take for the causal attention of the traced window's tokens in
every block that runs it — the layers here and the prediction module's —
(``benchmark/flops_glm47_flash.py``) over the summed device time of the Pallas calls
named ``flash_fwd``, mean over the cell's devices.  The blocks' remat keeps the kernel's
results, so each block calls it once a step.  Fails where the driver says flash is the
program's default and no such call is in the trace."""

import sys

from benchmark import flops_glm47_flash, scope_times

KERNELS, BACKWARD = ("flash_fwd",), False


def read(ctx, kernels=KERNELS, backward=BACKWARD):
    if ctx.trace is None or ctx.model.get("model_type") != "glm4_moe_lite":
        return None
    seconds = sum(scope_times.kernel_seconds_by_name(ctx.trace, k) for k in kernels)
    if seconds <= 0.0:
        if ctx.driver.default_attention() == "flash":
            raise RuntimeError(f"flash attention is the program's default and no Pallas call "
                               f"named {kernels} is in the trace; it holds {ctx.trace.pallas_calls()}")
        return None
    blocks = flops_glm47_flash.attention_blocks(ctx.model)
    length, per_device = int(ctx.traffic["sequence_length"]), ctx.sequences / ctx.chips
    itemsize = ctx.flops.BYTES[ctx.model["compute_dtype"]]
    need = blocks * flops_glm47_flash.attention_flops(ctx.model, per_device, length, backward)
    moved = blocks * flops_glm47_flash.attention_bytes(
        ctx.model, per_device, length, itemsize, backward)
    least, bound = ctx.flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"mla256 {'+'.join(kernels)}: {seconds:.4f} s on the device, least {least:.4f} s, "
          f"{bound}-bound", file=sys.stderr)
    return 100.0 * least / seconds
