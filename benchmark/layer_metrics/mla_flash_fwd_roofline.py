"""The flash forward kernel's share of its roofline in the MLA layers (q/k of
``qk_nope_head_dim + qk_rope_head_dim``, v of ``v_head_dim``): least time the
chip could take for the causal attention of the traced window's tokens
(``benchmark/flops_kimi_linear.py``, counted at the published 192 / 128, not at
the lane-padded width the kernel runs) over the summed device time of the
Pallas calls named ``flash_fwd``, mean over the cell's devices.  The layer's
recomputation in the backward pass runs the kernel a second time; its
operations are counted once.  Fails where the driver says flash is the
program's default and no such call is in the trace."""

import sys

from benchmark import flops_kimi_linear, scope_times

KERNELS, BACKWARD = ("flash_fwd",), False


def read(ctx, kernels=KERNELS, backward=BACKWARD):
    if ctx.trace is None or "linear_attn_config" not in ctx.model:
        return None
    seconds = sum(scope_times.kernel_seconds_by_name(ctx.trace, k) for k in kernels)
    if seconds <= 0.0:
        if ctx.driver.default_attention() == "flash":
            raise RuntimeError(f"flash attention is the program's default and no Pallas call "
                               f"named {kernels} is in the trace; it holds {ctx.trace.pallas_calls()}")
        return None
    layers = [m for m, _ in flops_kimi_linear.layer_kinds(ctx.model)].count("mla")
    length, per_device = int(ctx.traffic["sequence_length"]), ctx.sequences / ctx.chips
    itemsize = ctx.flops.BYTES[ctx.model["compute_dtype"]]
    need = layers * flops_kimi_linear.mla_attention_flops(ctx.model, per_device, length, backward)
    moved = layers * flops_kimi_linear.mla_attention_bytes(
        ctx.model, per_device, length, itemsize, backward)
    least, bound = ctx.flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"mla {'+'.join(kernels)}: {seconds:.4f} s on the device, least {least:.4f} s, "
          f"{bound}-bound", file=sys.stderr)
    return 100.0 * least / seconds
