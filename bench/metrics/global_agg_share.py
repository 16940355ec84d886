"""Device time of the global aggregation (eq. 2 and its broadcasts, Alg. 1
lines 3-9, program scope ``global_aggregation``) over device busy time in the
window (%)."""
import scopes


def read(ctx):
    return scopes.share(ctx, "global_aggregation")
