"""Pallas TPU kernel: fused BitParticle W8A8 matmul (exact / approximate).

TPU mapping of the paper's MAC unit (DESIGN.md §2):

  * exact mode — BitParticle's exact particlized MAC is bit-identical to an
    integer multiply, so one int8 x int8 -> int32 MXU contraction per block.
  * approx mode — the IR-group drop (groups {0} and {1,4}) factorizes into
    signed low-particle matmuls computed *in the same VMEM pass*:

        acc = A@W - A0@Wlow4 - 4*(A1@W0)

    with A0 = s(|A| & 3), A1 = s(|A|>>2 & 3), W0 = s(|W| & 3),
    Wlow4 = s(|W| & 15).  All three contractions run on int8 MXU tiles.

Grid is (M/bm, N/bn, K/bk) with the K dimension innermost ("arbitrary"
semantics): an int32 accumulator lives in VMEM scratch across K steps, and on
the last K step the dequant epilogue (per-row activation scale x per-channel
weight scale) is applied in-register before the single HBM writeback.

Block defaults (256, 256, 256) keep the working set ≈ 3 x 64 KiB int8 inputs
+ 256 KiB int32 accumulator — comfortably inside a v5e core's 16 MiB VMEM
with double-buffered pipelines, and all dims are multiples of the (32, 128)
int8 tile and the 128-wide MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _int8_dot(a, w):
    """int8 x int8 -> int32 MXU contraction of (bm, bk) x (bk, bn).

    The precision is pinned: the TPU compiler refuses an int8 matmul at a
    raised precision, which a process-wide ``jax_default_matmul_precision``
    would otherwise impose (the integer result is exact either way)."""
    return jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32
    )


def _signed_particles(x, mask):
    """sign(x) * (|x| & mask) as int8 (x is an int8 block)."""
    xi = x.astype(jnp.int32)
    s = jnp.sign(xi)
    return (s * (jnp.abs(xi) & mask)).astype(jnp.int8)


def _kernel(a_ref, w_ref, sa_ref, sw_ref, o_ref, acc_ref, *, n_k: int,
            approx: bool, fuse_dequant: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]  # (bm, bk) int8
    w = w_ref[...]  # (bk, bn) int8
    acc = _int8_dot(a, w)
    if approx:
        a0 = _signed_particles(a, 3)
        a1 = _signed_particles_shift2(a)
        w0 = _signed_particles(w, 3)
        wlow4 = _signed_particles(w, 15)
        acc = acc - _int8_dot(a0, wlow4) - 4 * _int8_dot(a1, w0)
    acc_ref[...] += acc

    @pl.when(k == n_k - 1)
    def _done():
        if fuse_dequant:
            o_ref[...] = (
                acc_ref[...].astype(jnp.float32) * sa_ref[...] * sw_ref[...]
            ).astype(o_ref.dtype)
        else:
            o_ref[...] = acc_ref[...]


def _signed_particles_shift2(x):
    """sign(x) * ((|x| >> 2) & 3) as int8."""
    xi = x.astype(jnp.int32)
    s = jnp.sign(xi)
    return (s * ((jnp.abs(xi) >> 2) & 3)).astype(jnp.int8)


@functools.partial(
    jax.jit,
    static_argnames=("approx", "fuse_dequant", "block_m", "block_n", "block_k",
                     "interpret"),
)
def bp_matmul_kernel(a_q, w_q, scale_a, scale_w, *, approx: bool = False,
                     fuse_dequant: bool = True, block_m: int = 256,
                     block_n: int = 256, block_k: int = 256,
                     interpret: bool = False):
    """Raw kernel invocation on pre-padded operands.

    a_q: (M, K) int8; w_q: (K, N) int8; scale_a: (M, 1) f32; scale_w: (1, N)
    f32.  M % block_m == K % block_k == N % block_n == 0 (use
    :mod:`.ops` for the padding wrapper).  Returns (M, N) f32 when
    ``fuse_dequant`` else int32.
    """
    m, k = a_q.shape
    k2, n = w_q.shape
    assert k == k2 and m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)
    kern = functools.partial(_kernel, n_k=n_k, approx=approx,
                             fuse_dequant=fuse_dequant)
    out_dtype = jnp.float32 if fuse_dequant else jnp.int32
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"bitparticle_matmul_{'approx' if approx else 'exact'}",
    )(a_q, w_q, scale_a, scale_w)
