"""Bytes the screening backend read from the device per window tick: the
``bytes`` each ``bocd.readback`` span in the window adds to the backend's
``d2h_bytes`` counter, which counts each device array once however often
the host reads it."""
from chipbench import spans


def read(ctx):
    run, ticks = spans.of_run(ctx), ctx.counters.get("ticks")
    total = run.id_sum("bocd.readback", "bytes") if run else None
    if total is None or not ticks:
        return None
    return total / ticks
