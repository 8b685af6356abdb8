"""Host time the screening backend spends blocked on device-to-host reads
per window tick, in ms: the ``bocd.readback`` spans, in whichever phase
of the screen they fall."""
from chipbench import spans


def read(ctx):
    return spans.ms_per(ctx, "ticks", "bocd.readback")
