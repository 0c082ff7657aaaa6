"""The SSD forward kernel's share of its roofline: least time the chip could take for the
scans of the traced window's tokens (``benchmark/flops_nemotron_h.py``: ``ssd_flops`` /
``ssd_bytes``, the scan's required work at the published ``chunk_size`` whatever
implements it) over the summed device time of the Pallas calls named ``ssd_fwd``, mean
over the cell's devices.  A call is found by NAME: an op-line event of a
``tpu_custom_call`` whose instruction's name holds the kernel's (XLA names the
instruction after the call's ``name=``, behind the transforms it was traced under:
``jvp_ssd_fwd_.1``, ``transpose_jvp_ssd_bwd__.1``).  Fails where the driver says the
kernels are the program's scan and no such call is in the trace."""

import sys

from benchmark import flops_nemotron_h

KERNEL, BACKWARD = "ssd_fwd", False


def kernel_seconds(trace, kernel: str) -> float:
    per = [sum(e.self_ns for e in events
               if kernel in e.name.partition(" = ")[0]
               and 'custom_call_target="tpu_custom_call"' in e.name) / 1e9
           for events in trace.ops.values()]
    return sum(per) / max(len(per), 1)


def read(ctx, kernel=KERNEL, backward=BACKWARD):
    if ctx.trace is None or "hybrid_override_pattern" not in ctx.model:
        return None
    seconds = kernel_seconds(ctx.trace, kernel)
    if seconds <= 0.0:
        if ctx.driver.default_scan() == "kernels":
            raise RuntimeError(f"the SSD kernels are the program's scan and no Pallas call "
                               f"named {kernel} is in the trace; it holds {ctx.trace.pallas_calls()}")
        return None
    length, per_device = int(ctx.traffic["sequence_length"]), ctx.sequences / ctx.chips
    itemsize = ctx.flops.BYTES[ctx.model["compute_dtype"]]
    need = flops_nemotron_h.ssd_flops(ctx.model, per_device, length, backward)
    moved = flops_nemotron_h.ssd_bytes(ctx.model, per_device, length, itemsize, backward)
    least, bound = ctx.flops.roofline_seconds(need, moved, ctx.peaks)
    print(f"{kernel}: {seconds:.4f} s on the device, least {least:.4f} s, {bound}-bound",
          file=sys.stderr)
    return 100.0 * least / seconds
