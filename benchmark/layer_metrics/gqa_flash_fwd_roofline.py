"""The flash forward kernel's share of its roofline under grouped kv heads, in
the windowed and the global layers together: least time the chip could take for
the attention of the traced window's tokens (``benchmark/flops_smallthinker.py``:
the band's pairs of a windowed layer, the causal half-square of a global one, q
and the output at ``num_attention_heads``, k and v at ``num_key_value_heads``)
over the summed device time of the Pallas calls named ``flash_fwd``, mean over
the cell's devices.  The layer's recomputation in the backward pass runs the
kernel a second time; its operations are counted once.  Fails where the driver
says flash is the program's default and no such call is in the trace."""

import sys

from benchmark import flops_smallthinker, scope_times

KERNELS, BACKWARD = ("flash_fwd",), False


def read(ctx, kernels=KERNELS, backward=BACKWARD):
    if ctx.trace is None or "sliding_window_layout" not in ctx.model:
        return None
    seconds = sum(scope_times.kernel_seconds_by_name(ctx.trace, k) for k in kernels)
    if seconds <= 0.0:
        if ctx.driver.default_attention() == "flash":
            raise RuntimeError(f"flash attention is the program's default and no Pallas call "
                               f"named {kernels} is in the trace; it holds {ctx.trace.pallas_calls()}")
        return None
    length, per_device = int(ctx.traffic["sequence_length"]), ctx.sequences / ctx.chips
    itemsize = ctx.flops.BYTES[ctx.model["compute_dtype"]]
    layers = flops_smallthinker.windowed(ctx.model)
    need = sum(flops_smallthinker.attention_flops(ctx.model, per_device, length, w, backward)
               for w in layers)
    moved = len(layers) * flops_smallthinker.attention_bytes(
        ctx.model, per_device, length, itemsize, backward)
    least, bound = ctx.flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"gqa {'+'.join(kernels)}: {seconds:.4f} s on the device, least {least:.4f} s, "
          f"{bound}-bound", file=sys.stderr)
    return 100.0 * least / seconds
