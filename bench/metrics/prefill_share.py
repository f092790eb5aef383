"""Serve loop: prefill dispatch time as a share of all dispatch time in
the window.  Moves ``itl_p99_ms``: a
prefill stalls every decoder, and the longest stall sets the p99 gap."""

from bench.stats import prefill_share


def read(ctx):
    return prefill_share(ctx["steps"])
