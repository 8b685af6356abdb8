"""Share of the candidates the fleet screen sent to its exact verification
in the window that it confirmed as flags, in %: how far ``FleetDetect``'s
``verify_confirmed`` and ``verify_attempts`` counters, which each
``fleet.tick`` span carries as of its start, moved from the window's first
tick to its last."""
from chipbench import spans


def read(ctx):
    run = spans.of_run(ctx)
    if run is None:
        return None
    attempts = run.id_delta("fleet.tick", "verify_attempts")
    confirmed = run.id_delta("fleet.tick", "verify_confirmed")
    if not attempts or confirmed is None:
        return None
    return 100.0 * confirmed / attempts
