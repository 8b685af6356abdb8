"""Device self time per window step of the collectives (ops named
``all-reduce*``, ``all-gather*``, ``reduce-scatter*``, ``all-to-all*`` or
``collective-permute*``), averaged over the chips, in ms."""
from chipbench import op_scopes


def read(ctx):
    return op_scopes.ms_per_step(ctx, "collective")
