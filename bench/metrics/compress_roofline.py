"""The compress kernel's share of its roofline (%): the least time its
messages need on this chip (``counts.compress_least_seconds``: one read and
one write of every entry, HBM-bound) over the kernel's device time in the
window."""
import counts
import harness as H


def read(ctx):
    f = ctx["facts"]
    if "compress_mats" not in f:
        return None
    TR = H.load_module(H.BENCH / "trace.py", "trace")
    seconds, n = TR.op_seconds(ctx["trace"], TR.KERNELS["compress"])
    if not n:
        return None
    least = counts.compress_least_seconds(f["compress_mats"], ctx["peaks"])["seconds"]
    return 100.0 * least * f["exchanges"] / seconds
