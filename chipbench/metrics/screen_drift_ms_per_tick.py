"""Host time of the fleet screen's drift screens per window tick, in ms:
the ``fleet.drift`` spans (trailing means, the lagged and long-horizon
screens) and the ``fleet.ewma`` spans (the long-horizon baseline's
update), children included."""
from chipbench import spans


def read(ctx):
    return spans.ms_per(ctx, "ticks", "fleet.drift", "fleet.ewma")
