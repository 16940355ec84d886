"""Mean device time of one call of the engine's decode executor
(``serve_decode``, one decode block of every slot), from the trace, ms."""
import harness as H


def read(ctx):
    module = ctx["facts"].get("decode_module")
    if module is None:
        return None
    TR = H.load_module(H.BENCH / "trace.py", "trace")
    times = TR.module_seconds(ctx["trace"], module)
    return 1e3 * sum(times) / len(times) if times else None
