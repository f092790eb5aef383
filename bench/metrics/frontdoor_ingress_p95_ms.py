"""Front door: 95th percentile, over the window's requests, of the time
from the front door reading a request's head to the serve loop's inbox
holding it (body read, parse, route; the loop's ``request`` records,
``ingress_s``).  None where the records carry no such field.  Moves
``ttft_p95_ms``."""

from bench.stats import percentile


def read(ctx):
    p = percentile([r["ingress_s"] for r in ctx["requests"]
                    if r.get("ingress_s") is not None], 95)
    return None if p is None else 1e3 * p
