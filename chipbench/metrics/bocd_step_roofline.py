"""The ``bocd_step`` kernel's share of its roofline: the HBM bytes one step
over the (slots x streams) float32 state needs (``chipbench.flops``) times
the steps run, over the peak HBM bandwidth, over the kernel's device time in
the trace, in %. The kernel is found by its stable name in the trace."""
from chipbench.flops import bocd_step_bytes

KERNEL = "bocd_step"


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = ctx.trace.kernel_s(KERNEL)
    if kernel_s <= 0:
        return None
    c = ctx.counters
    bytes_needed = bocd_step_bytes(c["slots"], c["streams"]) * c["ticks"]
    return 100.0 * bytes_needed / ctx.peaks["hbm_bytes_per_s"] / kernel_s
