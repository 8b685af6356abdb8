"""Host time per window step that FALCON's monitoring adds to the step
path, in ms: the ``train.simulate`` (the performance model's iteration
time), ``controlplane.observe`` and ``train.mitigate`` spans."""
from chipbench import spans


def read(ctx):
    return spans.ms_per(ctx, "steps", "train.simulate", "controlplane.observe",
                        "train.mitigate")
