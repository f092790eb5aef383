"""Front door: 99th percentile, over every token written to a client by
the window's requests, of the time from the token's commit in the serve
loop to the SSE write's drain returning (``egress_s``).  None where the
records carry no such samples.  Moves ``itl_p99_ms``."""

from bench.stats import percentile


def read(ctx):
    p = percentile([x for r in ctx["requests"]
                    for x in (r.get("egress_s") or ())], 99)
    return None if p is None else 1e3 * p
