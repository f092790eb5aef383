"""Serve loop: host time per decode step, from the window's step records:
the time the records span less every dispatch in them, over the decode
steps.  Moves ``itl_p99_ms``."""

from bench.stats import host_ms_per_step


def read(ctx):
    return host_ms_per_step(ctx["steps"])
