"""Pallas TPU kernel: chunked RWKV-6 WKV recurrence.  [arXiv:2404.05892]

Per (batch x head) row, per chunk of L timesteps (grid dims: rows parallel,
chunks sequential/"arbitrary"), with the (N, N) state carried in VMEM
scratch across chunk steps:

    lc_i   = sum_{s<i} log_w_s                  (per channel, <= 0)
    out_i  = (r_i * exp(lc_i)) . S              cross-chunk     (MXU)
           + sum_{j<i} (r_i . k_j * exp(lc_i - lc_{j+1})) v_j   (intra)
           + (r_i . u*k_i) v_i                  bonus
    S'     = diag(exp(lc_end)) S + sum_j (k_j exp(lc_end - lc_{j+1})) v_j^T

All pairwise decay exponents are <= 0 (numerically safe); the intra-chunk
pair tensor is (L, L, N) in VMEM (L=64, N=64 -> 1 MiB f32).  The state
update and cross-chunk terms are (L,N)x(N,N) MXU matmuls.

The layer-level win vs the pure-jnp chunked form: one VMEM-resident pass per
chunk (r/k/v/w streamed once from HBM, state never leaves VMEM), where the
XLA scan materializes the (L,L,N) pair tensor and carried state through HBM
each step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, out_ref, sout_ref,
            s_ref, *, n_chunks: int, chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = s0_ref[0]

    rc = r_ref[0].astype(jnp.float32)          # (L, N)
    kc = k_ref[0].astype(jnp.float32)
    vc = v_ref[0].astype(jnp.float32)
    wc = w_ref[0].astype(jnp.float32)          # log-decay, <= 0
    u = u_ref[0].astype(jnp.float32)           # (N,)
    s = s_ref[...]                             # (N, N)

    lc = jnp.cumsum(wc, axis=0) - wc           # lc_i = sum_{s<i}
    lcs = lc + wc                              # lc_{i+1}
    lc_end = lcs[-1]                           # (N,)

    # cross-chunk: (r * exp(lc)) @ S
    r_dec = rc * jnp.exp(lc)
    out = jnp.dot(r_dec, s, preferred_element_type=jnp.float32)

    # intra-chunk pairs (strictly lower triangular)
    pair = jnp.exp(lc[:, None, :] - lcs[None, :, :])       # (L, L, N)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = (lj < li)[:, :, None]
    a_mat = jnp.sum(rc[:, None, :] * kc[None, :, :]
                    * jnp.where(tri, pair, 0.0), axis=-1)  # (L, L)
    out = out + jnp.dot(a_mat, vc, preferred_element_type=jnp.float32)

    # bonus: current-token diagonal
    bonus = jnp.sum(rc * u[None, :] * kc, axis=-1)         # (L,)
    out = out + bonus[:, None] * vc
    out_ref[0] = out.astype(out_ref.dtype)

    # state update
    k_dec = kc * jnp.exp(lc_end[None, :] - lcs)
    s_new = jnp.exp(lc_end)[:, None] * s + jnp.dot(
        k_dec.T, vc, preferred_element_type=jnp.float32)
    s_ref[...] = s_new

    @pl.when(c == n_chunks - 1)
    def _done():
        sout_ref[0] = s_new


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def wkv6_kernel(r, k, v, log_w, u, state, *, chunk: int = 64,
                interpret: bool = False):
    """r/k/v/log_w: (R, T, N) with R = batch*heads; u: (R, N);
    state: (R, N, N) f32.  T % chunk == 0.  Returns (out (R,T,N) f32,
    state_out (R,N,N) f32)."""
    R, T, N = r.shape
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk
    kern = functools.partial(_kernel, n_chunks=n_chunks, chunk=chunk)
    grid = (R, n_chunks)
    seq_spec = pl.BlockSpec((1, chunk, N), lambda i, c: (i, c, 0))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, N), lambda i, c: (i, 0)),
                  pl.BlockSpec((1, N, N), lambda i, c: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, chunk, N), lambda i, c: (i, c, 0)),
                   pl.BlockSpec((1, N, N), lambda i, c: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((R, T, N), jnp.float32),
                   jax.ShapeDtypeStruct((R, N, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="wkv6_chunked",
    )(r, k, v, log_w, u, state)
