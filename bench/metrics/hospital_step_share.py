"""Device time of the hospital towers' local step (eqs. 5-6: the θ0/θ1
gradients and updates, program scope ``local_step/hospital``) over device
busy time in the window (%)."""
import scopes


def read(ctx):
    return scopes.share(ctx, "local_step/hospital")
