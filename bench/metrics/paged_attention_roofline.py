"""Kernel ``paged_attention`` (decode): the least time the chip needs to
read the KV pages each decode step attends over, over the kernel's
device time in those steps.  The context is taken from the step's
``blocks_in_use`` gauge as a lower bound, every block full except one
token in each active slot's tail block, so the share is never
overstated.  On a mesh, the whole step's work at one chip's peaks stands
against the kernel's time summed over the chips: the share of the chips'
combined roofline.  Moves ``itl_p99_ms``."""

from bench.costs import attention_decode_work, roofline_s
from bench.trace_reduce import kernel_ns, traced_steps

KERNEL = "paged_attention"


def read(ctx):
    steps = traced_steps(ctx, "decode")
    t_ns = kernel_ns(ctx["trace"], KERNEL, [iv for _, iv in steps]) \
        if steps else 0.0
    if t_ns <= 0:
        return None
    pk, conf = ctx["peaks"], ctx["conf"]
    bs = ctx["cell"]["serve"]["block_size"]
    need = 0.0
    for r, _ in steps:
        rows = r["active_slots"]
        ctx_tokens = max(r["blocks_in_use"] - rows, 0) * bs + rows
        need += roofline_s([attention_decode_work(conf, ctx_tokens, rows)],
                           pk["bf16_flops"], pk["hbm_bytes_per_s"])
    return 100.0 * need / (t_ns * 1e-9)
