"""Host time per window step spent making the batch and copying it to the
device, in ms: the ``train.batch`` spans."""
from chipbench import spans


def read(ctx):
    return spans.ms_per(ctx, "steps", "train.batch")
