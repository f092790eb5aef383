"""Quantized matmul backends with BitParticle numerics.

Three modes, selectable per layer / per config:

  ``bf16``      plain mixed-precision matmul (the unquantized baseline).
  ``bp_exact``  W8A8 sign-magnitude int8 matmul.  BitParticle's exact MAC is
                bit-identical to integer multiply (proven exhaustively in
                tests), so the TPU lowering is a single int8xint8->int32 MXU
                contraction + dequant epilogue.
  ``bp_approx`` the paper's approximate MAC (drops IR groups {0} and {1,4}).
                Using signed low particles A0 = s.(|A| & 3), A1 = s.(|A|>>2 & 3),
                W0 = s.(|W| & 3), Wlow4 = s.(|W| & 15):

                    approx(A @ W) = A@W - A0@Wlow4 - 4*(A1@W0)

                i.e. the elementwise IR-group drop factorizes into two extra
                int8 matmuls — the TPU-native formulation of the variant.

The Pallas TPU kernel in ``repro.kernels.bitparticle_matmul`` fuses all
contractions + dequant in one VMEM pass; this module holds both the pure-jnp
(XLA) implementation — used for training, dry-runs, and as the kernel oracle
— and the backend dispatch that routes inference-path contractions through
the kernel.

Backend selection (``matmul_backend`` on ``ArchConfig`` / this module):

  ``auto``              fused Pallas kernel on TPU, pure XLA elsewhere.
  ``kernel``            force the compiled Pallas kernel.
  ``kernel_interpret``  force the kernel in interpret mode (CPU validation).
  ``xla``               force the pure-jnp three-matmul formulation.

The active backend is a trace-time choice: ``use_matmul_backend`` scopes it
around a jit trace (the serving engine wraps every compiled entry point this
way), ``set_matmul_backend`` moves the process-wide default.
"""

from __future__ import annotations

import contextlib
import logging
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import quant
# hoisted (was a per-call import inside the hot dispatch path): sharding
# only imports jax/numpy, so there is no import cycle to dodge
from repro.distributed import sharding as _shd

_log = logging.getLogger(__name__)

MODES = ("bf16", "qat", "bp_exact", "bp_approx")
BACKENDS = ("auto", "xla", "kernel", "kernel_interpret")

_matmul_backend = "auto"


def set_matmul_backend(backend: str) -> str:
    """Set the process-wide quantized-matmul backend; returns the previous
    value.  Takes effect at trace time — already-compiled functions keep the
    backend they were traced with."""
    global _matmul_backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown matmul backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    prev = _matmul_backend
    _matmul_backend = backend
    return prev


def get_matmul_backend() -> str:
    return _matmul_backend


@contextlib.contextmanager
def use_matmul_backend(backend: str):
    """Scope the quantized-matmul backend around a trace/call."""
    prev = set_matmul_backend(backend)
    try:
        yield
    finally:
        set_matmul_backend(prev)


def resolve_matmul_backend(backend: str = None) -> str:
    """Concrete backend ("xla" | "kernel" | "kernel_interpret") for the
    current default device.

    Kernel backends stay valid verbatim under an active mesh trace: the
    dispatch sites wrap the Pallas kernels in ``shard_map`` over the active
    mesh (per-shard fused kernel + collective combine of partial results),
    so there is no blanket mesh -> "xla" downgrade here anymore.  The rare
    remaining per-call degrades (e.g. int8 KV scale pages, which only the
    gather oracle understands) announce themselves once through
    :func:`note_backend_fallback` instead of silently resolving away."""
    b = _matmul_backend if backend is None else backend
    if b == "auto":
        b = "kernel" if jax.default_backend() == "tpu" else "xla"
    return b


def mesh_active() -> bool:
    """True when a mesh is active for the current trace (resolved once per
    trace at each dispatch site — cached executions pay nothing)."""
    return _shd.current_mesh() is not None


#: one-time fallback ledger: reason -> count.  The first occurrence of each
#: reason logs a warning; every occurrence is counted so telemetry/tests can
#: assert whether (and why) a kernel request degraded to the XLA oracle.
_FALLBACK_NOTES: dict = {}


def note_backend_fallback(reason: str) -> None:
    """Record (and log, first time per reason) a backend downgrade."""
    n = _FALLBACK_NOTES.get(reason, 0)
    _FALLBACK_NOTES[reason] = n + 1
    if n == 0:
        _log.warning("quantized-op backend fallback: %s "
                     "(further occurrences counted, not logged)", reason)


def backend_fallbacks() -> dict:
    """Snapshot of the fallback ledger ({reason: count})."""
    return dict(_FALLBACK_NOTES)


def clear_backend_fallbacks() -> None:
    _FALLBACK_NOTES.clear()


def signed_low_particles(q):
    """(q0, q1, qlow4): signed particles of the two low 2-bit groups.

    q0 = sign(q)*(|q| & 3), q1 = sign(q)*((|q| >> 2) & 3),
    qlow4 = sign(q)*(|q| & 15) = q0 + 4*q1.  All int8-range int32 arrays.
    """
    q = jnp.asarray(q, jnp.int32)
    s = jnp.sign(q)
    m = jnp.abs(q)
    q0 = s * (m & 3)
    q1 = s * ((m >> 2) & 3)
    return q0, q1, q0 + 4 * q1


def int_matmul(a_q, w_q):
    """int8 x int8 -> int32 contraction over the last/first axes (MXU-native)."""
    return jax.lax.dot_general(
        a_q.astype(jnp.int8), w_q.astype(jnp.int8),
        (((a_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def bp_matmul_int(a_q, w_q, mode: str = "bp_exact"):
    """Integer-domain BitParticle matmul: int8 operands -> int32 accumulators."""
    acc = int_matmul(a_q, w_q)
    if mode == "bp_exact":
        return acc
    if mode == "bp_approx":
        a0, a1, _ = signed_low_particles(a_q)
        w0, _, wlow4 = signed_low_particles(w_q)
        corr = int_matmul(a0, wlow4) + 4 * int_matmul(a1, w0)
        return acc - corr
    raise ValueError(f"unknown integer mode: {mode}")


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def quantized_matmul(x, w, w_scale, mode: str):
    """Dequantizing BitParticle matmul with a straight-through gradient.

    x: (..., K) float; w: (K, N) int8 (pre-quantized, per-channel w_scale (N,)).
    Activations are dynamically quantized PER ROW (one symmetric scale per
    token position): each row's numerics are then independent of whatever
    else shares the batch, so a token produces bit-identical logits whether
    it is decoded alone, in a continuous batch, or inside a multi-token
    speculative verify window — the invariant the serving token-identity
    guarantees stand on.  Returns (..., N) in x.dtype.
    """
    return _qmm_fwd_impl(x, w, w_scale, mode)


def _qmm_fwd_impl(x, w, w_scale, mode):
    x_scale = quant.compute_scale(x, axis=(-1,))   # (..., 1) per-row
    x_q = quant.quantize(x, x_scale)
    backend = resolve_matmul_backend()
    if backend != "xla" and mode in ("bp_exact", "bp_approx"):
        # fused Pallas path: quantize-scale plumbing + exact/approx
        # contractions + dequant epilogue in one VMEM pass.  Under an
        # active mesh the kernel runs per-shard inside shard_map (TP
        # column split / split-K psum combine) instead of degrading to XLA.
        interpret = backend == "kernel_interpret"
        mesh = _shd.current_mesh()
        if mesh is not None:
            from repro.kernels.bitparticle_matmul.ops import bp_matmul_sharded
            out = bp_matmul_sharded(x_q, w, x_scale, w_scale,
                                    approx=(mode == "bp_approx"),
                                    interpret=interpret, mesh=mesh)
        else:
            from repro.kernels.bitparticle_matmul.ops import bp_matmul
            out = bp_matmul(x_q, w, x_scale, w_scale,
                            approx=(mode == "bp_approx"),
                            interpret=interpret)
        return out.astype(x.dtype)
    acc = bp_matmul_int(x_q, w, mode)
    # the kernel's epilogue order, (acc * x_scale) * w_scale, so both
    # backends round the dequantized product alike
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(x.dtype)


def _qmm_fwd(x, w, w_scale, mode):
    return _qmm_fwd_impl(x, w, w_scale, mode), (x, w, w_scale)


def _qmm_bwd(mode, res, g):
    x, w, w_scale = res
    # STE through quantization: grads flow as if the matmul were x @ (w*ws).
    w_f = w.astype(g.dtype) * w_scale.astype(g.dtype)
    gx = jnp.einsum("...n,kn->...k", g, w_f)
    gw = jnp.zeros_like(w)  # int weights are not trained through this path
    gws = jnp.zeros_like(w_scale)
    return gx, gw, gws


quantized_matmul.defvjp(_qmm_fwd, _qmm_bwd)


@jax.custom_vjp
def quantized_gather(w):
    """Per-channel int8 quantize -> replicate (the collective moves int8) ->
    dequantize.  STE backward: the cotangent passes straight to the sharded
    weight (its resharding transposes to a reduce-scatter under SPMD).

    This is the paper's W8 quantization applied to the *FSDP all-gather
    wire format*: weight-gather bytes halve vs bf16 (EXPERIMENTS.md §Perf B).
    """
    from repro.distributed.sharding import force_replicated
    scale = quant.compute_scale(w.astype(jnp.float32), axis=(0,))
    q = quant.quantize(w.astype(jnp.float32), scale)
    q = force_replicated(q)
    return (q.astype(jnp.float32) * scale).astype(w.dtype)


def _qg_fwd(w):
    return quantized_gather(w), None


def _qg_bwd(_, g):
    return (g,)


quantized_gather.defvjp(_qg_fwd, _qg_bwd)


def dense_apply(x, w_f, mode: str, *, precision=None):
    """Dense layer forward used by all models: float weights, mode-dependent.

    ``bf16``: plain matmul.  Quantized modes: weights are per-channel
    fake-routed through int8 (dynamic quantization of both operands) so that
    dry-run HLO contains the true int8 contraction graph.  For training the
    straight-through estimator keeps the graph differentiable.
    The ``+q8gather`` suffix routes the weight through
    :func:`quantized_gather` first (int8 on the FSDP gather wire).
    """
    if mode.endswith("+q8gather"):
        w_f = quantized_gather(w_f)
        mode = mode[: -len("+q8gather")]
    if w_f.dtype == jnp.int8:
        # pre-quantized serving weights: the scale rides along in the params
        raise ValueError("int8 weights must go through dense() with w_scale")
    if mode == "bf16":
        return jnp.einsum("...k,kn->...n", x, w_f, precision=precision)
    if mode == "qat":
        # Quantization-aware training: fake-quant both operands (STE grads
        # flow to the float weights), float MXU matmul.
        ws = quant.compute_scale(w_f.astype(jnp.float32), axis=(0,))
        w_fq = quant.fake_quant(w_f.astype(jnp.float32), ws).astype(x.dtype)
        xs = quant.compute_scale(x.astype(jnp.float32))
        x_fq = quant.fake_quant(x.astype(jnp.float32), xs).astype(x.dtype)
        return jnp.einsum("...k,kn->...n", x_fq, w_fq, precision=precision)
    w_q, w_scale = quant.quantize_per_channel(w_f.astype(jnp.float32), channel_axis=-1)
    w_scale = w_scale.reshape(-1)  # (N,)
    from repro.core import probe
    probe.record_activation(x)
    return quantized_matmul(x, w_q, w_scale, mode)
