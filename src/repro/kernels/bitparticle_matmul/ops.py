"""Jitted public wrapper around the BitParticle matmul Pallas kernel.

Handles arbitrary leading batch dims, non-block-aligned shapes (zero padding
— zeros contribute nothing in either exact or approx mode), scale plumbing,
and the interpret-mode fallback used for CPU validation.

:func:`bp_matmul_sharded` is the mesh entry point: it wraps the same kernel
in ``shard_map`` over the active ("data","model") mesh, picking a tensor-
parallel strategy per call — output-column split (zero collectives),
split-K with an exact int32 psum combine, or replicated compute when
neither contraction dim divides — so ``matmul_backend="kernel"`` stays
valid verbatim on the mesh executor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.bitparticle_matmul.kernel import bp_matmul_kernel


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_block(dim: int, pref: int, align: int) -> int:
    """Block that minimizes padded work, not just the largest one.

    Among `align`-multiples <= pref, pick the block whose grid covers ``dim``
    with the least padding (ties break toward the larger block — fewer grid
    steps).  Always taking ``pref`` nearly doubles the FLOPs when a dim sits
    just past it: M=257 under pref=256 pads to 512, while block 128 pads to
    384.  (`align`-aligned in spirit — interpret mode relaxes hardware
    tiling.)"""
    if dim <= align:
        return align
    best_b, best_pad = align, _round_up(dim, align)
    for b in range(align, pref + 1, align):
        pad = _round_up(dim, b)
        if pad < best_pad or (pad == best_pad and b > best_b):
            best_b, best_pad = b, pad
    return best_b


@functools.partial(
    jax.jit,
    static_argnames=("approx", "block_m", "block_n", "block_k", "interpret"),
)
def bp_matmul(a_q, w_q, scale_a=None, scale_w=None, *, approx: bool = False,
              block_m: int = 256, block_n: int = 256, block_k: int = 256,
              interpret: bool = False):
    """BitParticle quantized matmul.

    a_q: (..., K) int8 activations; w_q: (K, N) int8 weights.
    scale_a: None | scalar | (...,) per-row f32; scale_w: None | (N,) f32.
    Returns f32 (..., N) if any scale given (fused dequant), else int32.
    """
    *lead, k = a_q.shape
    k2, n = w_q.shape
    assert k == k2, (a_q.shape, w_q.shape)
    m = 1
    for d in lead:
        m *= d
    a2 = a_q.reshape(m, k)

    fuse = scale_a is not None or scale_w is not None
    if scale_a is None:
        sa = jnp.ones((m, 1), jnp.float32)
    else:
        sa = jnp.broadcast_to(jnp.asarray(scale_a, jnp.float32).reshape(-1, 1)
                              if jnp.ndim(scale_a) > 0 else
                              jnp.full((m, 1), scale_a, jnp.float32), (m, 1))
    sw = (jnp.ones((1, n), jnp.float32) if scale_w is None
          else jnp.asarray(scale_w, jnp.float32).reshape(1, n))

    bm = _pick_block(m, block_m, 8)
    bn = _pick_block(n, block_n, 128)
    bk = _pick_block(k, block_k, 128)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)

    a_pad = jnp.pad(a2, ((0, mp - m), (0, kp - k)))
    w_pad = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
    sa_pad = jnp.pad(sa, ((0, mp - m), (0, 0)), constant_values=1.0)
    sw_pad = jnp.pad(sw, ((0, 0), (0, np_ - n)), constant_values=1.0)

    out = bp_matmul_kernel(
        a_pad, w_pad, sa_pad, sw_pad, approx=approx, fuse_dequant=fuse,
        block_m=bm, block_n=bn, block_k=bk, interpret=interpret,
    )
    return out[:m, :n].reshape(*lead, n)


def _matmul_strategy(lead, k: int, n: int, axes: dict):
    """(batch_axis, strategy) for one sharded matmul call.

    strategy: "col" — weight columns over "model", per-shard fused kernel,
    no collectives (the bit-exact fast path; applies whenever N divides);
    "splitk" — contraction dim over "model", int32 psum combine (exact:
    integer partial sums commute); "rep" — replicated compute.  The batch
    axis additionally splits the leading dim over "data" when it divides.
    """
    model = axes.get("model", 1)
    data = axes.get("data", 1)
    batch_axis = ("data" if lead and data > 1 and lead[0] % data == 0
                  else None)
    if model > 1 and n % model == 0:
        return batch_axis, "col"
    if model > 1 and k % model == 0:
        return batch_axis, "splitk"
    return batch_axis, "rep"


def bp_matmul_sharded(a_q, w_q, scale_a=None, scale_w=None, *,
                      approx: bool = False, interpret: bool = False, mesh):
    """BitParticle quantized matmul partitioned over an active mesh.

    Same numerics contract as :func:`bp_matmul` with scales (always returns
    the dequantized f32 result), but the kernel runs per shard inside
    ``shard_map`` over ``mesh``.  Strategy is chosen from the shapes (see
    :func:`_matmul_strategy`); both the column split and the split-K psum
    keep integer accumulation exact, so the result matches the unsharded
    kernel's dequant epilogue ``acc * scale_a * scale_w`` bit-for-bit.
    """
    from repro.distributed import sharding as shd

    *lead, k = a_q.shape
    k2, n = w_q.shape
    assert k == k2, (a_q.shape, w_q.shape)
    axes = shd.mesh_axes_dict(mesh)
    batch_axis, strategy = _matmul_strategy(lead, k, n, axes)

    sa = (jnp.ones((*lead, 1), jnp.float32) if scale_a is None
          else jnp.broadcast_to(jnp.asarray(scale_a, jnp.float32),
                                (*lead, 1)))
    sw = (jnp.ones((n,), jnp.float32) if scale_w is None
          else jnp.asarray(scale_w, jnp.float32).reshape(n))

    lead_spec = (batch_axis,) + (None,) * (len(lead) - 1) if lead else ()
    a_spec = P(*lead_spec, "model" if strategy == "splitk" else None)
    w_spec = P("model" if strategy == "splitk" else None,
               "model" if strategy == "col" else None)
    sa_spec = P(*lead_spec, None)
    sw_spec = P("model" if strategy == "col" else None)
    out_spec = P(*lead_spec, "model" if strategy == "col" else None)

    def run(aq, wq, sa, sw):
        if strategy == "splitk":
            acc = bp_matmul(aq, wq, approx=approx, interpret=interpret)
            acc = shd.combine_matmul_partials(acc, "model")
            # dequant epilogue after the exact int32 combine, in the same
            # order as the kernel's fused epilogue (acc * sa * sw)
            return acc.astype(jnp.float32) * sa * sw
        return bp_matmul(aq, wq, sa, sw, approx=approx, interpret=interpret)

    fn = shd.shard_map(
        run, mesh=mesh, in_specs=(a_spec, w_spec, sa_spec, sw_spec),
        out_specs=out_spec)
    return fn(a_q, w_q, sa, sw)
