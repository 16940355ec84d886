"""Device time of the ops under no program scope (XLA's copies, loop
bookkeeping) over device busy time in the window (%): what the program's
scopes leave unnamed."""
import scopes


def read(ctx):
    return scopes.share(ctx, scopes.UNSCOPED)
