"""The block-diffusion forward kernel's share of its roofline: least time the chip could
take for the attention of the traced window's tokens (``benchmark/flops_sdar.py``:
``L (L + B)`` pairs a head a sequence in every layer, both halves, at
``num_attention_heads`` query heads over ``num_key_value_heads`` kv heads; q and the
output at the query heads, the clean and the noised k and v at the kv heads) over the
summed device time of the Pallas calls named ``bd_flash_fwd``, mean over the cell's
devices.  The layer's recomputation in the backward pass runs the kernel a second time
where the remat keeps nothing; its operations are counted once.  Fails where the driver
says flash is the program's default and no such call is in the trace."""

import sys

from benchmark import flops_sdar, scope_times

KERNELS, BACKWARD = ("bd_flash_fwd",), False


def read(ctx, kernels=KERNELS, backward=BACKWARD):
    if ctx.trace is None or "block_length" not in ctx.model:
        return None
    seconds = sum(scope_times.kernel_seconds_by_name(ctx.trace, k) for k in kernels)
    if seconds <= 0.0:
        if ctx.driver.default_attention() == "flash":
            raise RuntimeError(f"flash attention is the program's default and no Pallas call "
                               f"named {kernels} is in the trace; it holds {ctx.trace.pallas_calls()}")
        return None
    length, per_device = int(ctx.traffic["sequence_length"]), ctx.sequences / ctx.chips
    itemsize = ctx.flops.BYTES[ctx.model["compute_dtype"]]
    need = flops_sdar.kernel_flops(ctx.model, per_device, length, backward)
    moved = flops_sdar.kernel_bytes(ctx.model, per_device, length, itemsize, backward)
    least, bound = ctx.flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"bd {'+'.join(kernels)}: {seconds:.4f} s on the device, least {least:.4f} s, "
          f"{bound}-bound", file=sys.stderr)
    return 100.0 * least / seconds
