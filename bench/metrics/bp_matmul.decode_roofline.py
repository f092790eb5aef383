"""Kernel ``bitparticle_matmul`` in decode steps: the least time the chip
needs for the steps' int8 contractions (rows = active slots), over the
kernel's device time in those steps.  On a mesh, the whole step's work
at one chip's peaks stands against the kernel's time summed over the
chips: the share of the chips' combined roofline.  Moves
``itl_p99_ms``."""

from bench.costs import int8_contractions, roofline_s
from bench.trace_reduce import kernel_ns, traced_steps

KERNEL = "bitparticle_matmul"


def read(ctx):
    steps = traced_steps(ctx, "decode")
    t_ns = kernel_ns(ctx["trace"], KERNEL, [iv for _, iv in steps]) \
        if steps else 0.0
    if t_ns <= 0:
        return None
    pk = ctx["peaks"]
    need = sum(roofline_s(int8_contractions(ctx["conf"], r["active_slots"]),
                          pk["int8_ops"], pk["hbm_bytes_per_s"])
               for r, _ in steps)
    return 100.0 * need / (t_ns * 1e-9)
