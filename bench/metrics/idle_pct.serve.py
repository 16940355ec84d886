"""Share of the traced serving window in which the device ran no op (%):
1 - busy / window, busy being the union of the chip's op intervals."""


def read(ctx):
    t = ctx["trace"]
    if "flops_per_token" not in ctx["facts"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
