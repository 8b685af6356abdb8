"""Backend compiles inside the measured window (``jax.monitoring``'s
compile events, counted by ``chipbench.harness.CompileLog``)."""


def read(ctx):
    return ctx.counters.get("compiles_in_window")
