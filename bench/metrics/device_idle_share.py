"""Device: share of the traced window in which no operation ran on the
chip.  Moves ``itl_p99_ms``."""

from bench.trace_reduce import idle_share


def read(ctx):
    if ctx.get("trace") is None:
        return None
    return 100.0 * idle_share(ctx["trace"])
