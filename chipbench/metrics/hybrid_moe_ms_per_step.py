"""Device self time per window step of the ops under the program's ``moe``
scope (routing, dispatch, the held experts' grouped products and the
combine, forward and backward; the shared expert and collectives left
out), averaged over the chips, in ms."""
from chipbench import op_scopes


def read(ctx):
    return op_scopes.ms_per_step(ctx, "moe")
