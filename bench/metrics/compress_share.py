"""Compress-kernel device time over device busy time in the window (%)."""
import harness as H


def read(ctx):
    TR = H.load_module(H.BENCH / "trace.py", "trace")
    seconds, n = TR.op_seconds(ctx["trace"], TR.KERNELS["compress"])
    if not n:
        return None
    return 100.0 * seconds / ctx["trace"]["busy_s"]
