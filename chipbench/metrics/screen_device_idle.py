"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window), in %."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
