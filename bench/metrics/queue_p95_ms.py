"""95th percentile over the window's requests of the wait from a request's
due time to its admission by the engine's scheduler (``t_admit``), ms."""


def read(ctx):
    return ctx["facts"].get("queue_p95_ms")
