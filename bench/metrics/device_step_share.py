"""Device time of the device towers' local step (eq. 7: the per-device θ2
gradients and update, program scope ``local_step/device``) over device busy
time in the window (%)."""
import scopes


def read(ctx):
    return scopes.share(ctx, "local_step/device")
