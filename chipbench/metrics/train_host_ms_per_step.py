"""Host time per training step outside the step call: the window's wall
time less the trainer's own measured step spans (``StepRecord.measured``,
dispatch to loss on the host), per step, in ms."""


def read(ctx):
    c = ctx.counters
    if not c.get("steps"):
        return None
    return (c["window_s"] - c["measured_s"]) / c["steps"] * 1e3
