"""Device self time per window step of the ops under the program's
``mamba`` scope (the Mamba-2 mixers, forward and backward, collectives
left out), averaged over the chips, in ms."""
from chipbench import op_scopes


def read(ctx):
    return op_scopes.ms_per_step(ctx, "mamba")
