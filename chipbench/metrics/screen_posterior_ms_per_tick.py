"""Host time of the fleet screen's posterior phase per window tick, in ms:
the ``fleet.posterior`` spans (``p_recent_change`` with its device reads,
and the threshold), children included."""
from chipbench import spans


def read(ctx):
    return spans.ms_per(ctx, "ticks", "fleet.posterior")
