"""The flash-attention forward kernel's share of its roofline: least time the
chip could take for the attention of the traced window (ops and bytes from
shapes alone, ``benchmark/flops.py``) over the kernel's summed device time in
the trace, mean over the cell's devices.

The kernel is found by its arity and by its first result's shape, which is
the traffic's own: [batch x heads, length, head size] of one step on one
device.  Where the driver says that the program's default attention is this
kernel and nothing in the trace matches, the run fails: a kernel that runs
and is no longer read is a fault of this reader, not silence.  Silent only
where the kernel is not the program's default (off the chip, or once a PR
has taken it off the path)."""

import sys

# ``_flash_kernel``: q, k, v in; o and the row statistics out
KERNELS = ((3, 2),)
BACKWARD = False


def read(ctx, kernels=KERNELS, backward=BACKWARD):
    if ctx.trace is None:
        return None
    heads = ctx.model["num_attention_heads"]
    head_dim = ctx.model["hidden_size"] // heads
    length = int(ctx.traffic["sequence_length"])
    shape = (int(ctx.traffic["batch_sequences"]) * heads, length, head_dim)
    seconds = ctx.trace.kernel_seconds(kernels, shape)
    if seconds <= 0.0:
        if ctx.driver.default_attention() == "flash":
            raise RuntimeError(
                f"flash attention is the program's default and no Pallas call of arity "
                f"{kernels} giving {shape} is in the trace; it holds {ctx.trace.pallas_calls()}")
        return None
    layers = ctx.model["num_hidden_layers"]
    itemsize = ctx.flops.BYTES[ctx.model["compute_dtype"]]
    per_device = ctx.sequences / ctx.chips
    need = layers * ctx.flops.attention_flops(per_device, heads, length, head_dim, True, backward)
    moved = layers * ctx.flops.attention_bytes(per_device, heads, length, head_dim, itemsize, backward)
    least, bound = ctx.flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"{'flash_bwd' if backward else 'flash_fwd'}: {seconds:.4f} s on the device, "
          f"least {least:.4f} s, {bound}-bound", file=sys.stderr)
    return 100.0 * least / seconds
