"""Device time of the exchange (Alg. 1 lines 10-21, program scope
``exchange``: local aggregation, the A_m/ξ_m draw, the ζ forward passes and
compression, the compress kernel included) over device busy time in the
window (%)."""
import scopes


def read(ctx):
    return scopes.share(ctx, "exchange")
