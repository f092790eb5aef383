"""Executor / model step: model operations of the rows each decode step
carried (every linear contraction, output head included; attention left
out), over the decode steps' wall time at the int8 peak of the cell's
chips (``ctx["chips"]``: the devices of its mesh, 1 without one).  Moves
``itl_p99_ms``."""

from bench.costs import matmul_ops_per_token


def read(ctx):
    steps = [s for s in ctx["steps"] if s["kind"] == "decode"]
    wall = sum(s["wall_s"] for s in steps)
    if not steps or wall <= 0:
        return None
    ops = matmul_ops_per_token(ctx["conf"], with_head=True) * sum(
        s["active_slots"] for s in steps)
    return 100.0 * ops / (wall * ctx["chips"] * ctx["peaks"]["int8_ops"])
