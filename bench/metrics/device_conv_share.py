"""Device time of the device towers' conv stack, forward and backward, laid
out with the devices on the lanes (program scope ``local_step/device/conv``,
inside ``local_step/device``) over device busy time in the window (%). None
where the program has no such scope or its path did not run."""
import scopes


def read(ctx):
    return scopes.share(ctx, "local_step/device/conv")
