"""Host time of the fleet screen's flag phase per window tick, in ms: the
``fleet.flags`` spans (``map_runlength`` with its device reads, and the
exact verification of each candidate), children included."""
from chipbench import spans


def read(ctx):
    return spans.ms_per(ctx, "ticks", "fleet.flags")
