"""Scheduler admission: 95th percentile of the wall time from queue entry
to admission over the window's requests (the loop's ``request``
records).  Moves ``ttft_p95_ms``."""

from bench.stats import percentile


def read(ctx):
    waits = [r["queue_wait_s"] for r in ctx["requests"]
             if r.get("queue_wait_s") is not None]
    p = percentile(waits, 95)
    return None if p is None else 1e3 * p
