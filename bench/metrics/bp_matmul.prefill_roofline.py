"""Kernel ``bitparticle_matmul`` in prefill steps: the least time the
chip needs for the layers' int8 contractions over the prompt tokens (no
padding) and, when untied, the output head over one row per request,
over the kernel's device time in those steps.  On a mesh, the whole
step's work at one chip's peaks stands against the kernel's time summed
over the chips: the share of the chips' combined roofline.  Moves
``itl_p99_ms``: a prefill stalls every decoder, and the longest stall
sets the p99 gap."""

from bench.costs import int8_contractions, roofline_s
from bench.trace_reduce import kernel_ns, traced_steps

KERNEL = "bitparticle_matmul"


def read(ctx):
    steps = traced_steps(ctx, "prefill")
    t_ns = kernel_ns(ctx["trace"], KERNEL, [iv for _, iv in steps]) \
        if steps else 0.0
    if t_ns <= 0:
        return None
    pk, conf = ctx["peaks"], ctx["conf"]
    need = 0.0
    for r, _ in steps:
        body = int8_contractions(dict(conf, tie_word_embeddings=True),
                                 r["prompt_tokens"])
        need += roofline_s(body, pk["int8_ops"], pk["hbm_bytes_per_s"])
        if not conf["tie_word_embeddings"]:
            head = int8_contractions(conf, r["group_size"])[-1:]
            need += roofline_s(head, pk["int8_ops"], pk["hbm_bytes_per_s"])
    return 100.0 * need / (t_ns * 1e-9)
