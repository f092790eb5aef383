"""Client: the 95th percentile of TTFT over the window's requests, taken
as the end-to-end ``ttft_p95_ms`` is, in the cells where host stalls make
it too unsteady to hold to a bound.  Moves ``itl_p99_ms``: both are set by
the prefill steps."""

import math


def read(ctx):
    v = ctx["client"]["ttft_p95_ms"]
    return v if math.isfinite(v) else None
