"""Serve loop: 95th percentile, over the window's requests, of the time
from the loop's inbox holding a request to its waiting queue holding it:
the wait for the next inbox drain (``inbox_s``) plus the hold in the
arrivals until they are submitted after the iteration's decode step
(``hold_s``).  None where the records carry no such fields.  Moves
``ttft_p95_ms``."""

from bench.stats import percentile


def read(ctx):
    p = percentile([r["inbox_s"] + r["hold_s"] for r in ctx["requests"]
                    if r.get("inbox_s") is not None
                    and r.get("hold_s") is not None], 95)
    return None if p is None else 1e3 * p
