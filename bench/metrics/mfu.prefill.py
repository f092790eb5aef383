"""Executor / model step: model operations of the prompt tokens each
prefill step carried (padding not counted; the output head once per
request; attention left out), over the prefill steps' wall time at the
int8 peak of the cell's chips (``ctx["chips"]``: the devices of its mesh,
1 without one).  Moves ``itl_p99_ms``: a prefill stalls every decoder,
and the longest stall sets the p99 gap."""

from bench.costs import head_matmul, matmul_ops_per_token


def read(ctx):
    steps = [s for s in ctx["steps"] if s["kind"] == "prefill"]
    wall = sum(s["wall_s"] for s in steps)
    if not steps or wall <= 0:
        return None
    k, n = head_matmul(ctx["conf"])
    ops = sum(s["prompt_tokens"] * matmul_ops_per_token(
        ctx["conf"], with_head=False) + s["group_size"] * 2.0 * k * n
        for s in steps)
    return 100.0 * ops / (wall * ctx["chips"] * ctx["peaks"]["int8_ops"])
