"""Pallas TPU kernel: paged-attention decode over block-table-indexed KV.

One decode query per sequence attends over K/V stored in fixed-size blocks
(``block_size`` tokens each) scattered across a physical page pool; the
per-sequence **block table** maps logical block index -> physical page.  The
block tables and valid lengths ride in as *scalar prefetch* operands
(``pltpu.PrefetchScalarGridSpec``), so the page gather is expressed in the
``index_map`` of the K/V BlockSpecs — each grid step DMAs exactly one
physical page into VMEM, and no gathered (B, T, ...) copy of the cache is
ever materialized in HBM (the XLA fallback in :mod:`.ref` does materialize
one; that is the memory the kernel saves).

Grid is ``(B, KV_heads, n_pages)`` with the page dimension innermost
("arbitrary" semantics): the online-softmax state (m, l, acc) for one
(sequence, kv-head) lives in VMEM scratch across page steps, and the output
is written once on the last page step.  Positions ``pos <= lengths[b]`` are
valid (the just-written token's K/V included), matching
``models/attention.py::decode_attention``.  Unused table entries point at
page 0 (the pool's trash block); their scores are masked to -inf before the
softmax so they contribute exactly 0.

Pages are laid out token-major, ``(L, N, block_size, KV_heads * D)``, one
stack for every layer: a token's K (or V) row of all its KV heads is one
contiguous row, so the decode step writes a new token's row in place into
the pool it carries through the layer scan.
The kernel reads head ``h``'s ``(block_size, D)`` tile as column block ``h``
of the row-major page, and takes the layer as a third scalar-prefetch
operand, so it reads straight from the stacked pool: no per-layer slice or
relayout copy of the pool is ever made.  Compiled for the chip,
``block_size`` must be a multiple of 8 and D a multiple of 128; interpret
mode (the CPU validation path) relaxes all tiling.  VMEM working set per
step: one K tile + one V tile + the (G, D) accumulator, a few KiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, *refs,
            block_size: int, n_pages: int, return_state: bool):
    if return_state:
        o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, D), pre-scaled
    k = k_ref[...].astype(jnp.float32)           # (block_size, D)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bs)
    pos = p * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    valid = pos <= len_ref[b]                    # (1, bs)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                          # (G, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # masked entries exponentiate to exactly 0 (guarded against the
    # all-masked-page case where s - m_new could be 0 - 0)
    pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_prev * corr + pexp.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (G, D)

    @pl.when(p == n_pages - 1)
    def _done():
        if return_state:
            # hand the raw flash-decoding state to the caller: shards of a
            # split-KV mesh run combine (m, l, acc) across shards before
            # normalizing (sharding.combine_softmax_state)
            o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)
            mo_ref[0, 0] = m_ref[...]
            lo_ref[0, 0] = l_ref[...]
        else:
            o_ref[0, 0] = (acc_ref[...] /
                           jnp.maximum(l_ref[...], 1e-37)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "return_state"))
def paged_attention_kernel(q, k_pages, v_pages, block_tables, lengths,
                           layer=0, *, interpret: bool = False,
                           return_state: bool = False):
    """q: (B, H, D); k_pages/v_pages: (L, N, block_size, KH * D), the
    stacked pool of every layer; block_tables: (B, n_pages) int32 physical
    page ids; lengths: (B,) int32 last valid position (inclusive); layer:
    int32 scalar, the layer whose pages are read.  Returns (B, H, D) in
    q.dtype.

    With ``return_state=True`` the normalization epilogue is skipped and the
    call returns the online-softmax partial state ``(acc, m, l)`` — acc
    (B, KH, G, D) f32 unnormalized, m/l (B, KH, G, 1) f32 — for a cross-
    shard split-KV combine.  A caller whose table covers only masked
    positions gets m = -inf, l = 0, acc = 0 (a neutral element)."""
    B, H, D = q.shape
    _, _, bs, F = k_pages.shape
    KH = F // D
    G = H // KH
    n_pages = block_tables.shape[1]
    scale = D ** -0.5
    qr = (q.astype(jnp.float32) * scale).reshape(B, KH, G, D)

    kern = functools.partial(_kernel, block_size=bs, n_pages=n_pages,
                             return_state=return_state)
    out_block = pl.BlockSpec((1, 1, G, D),
                             lambda b, h, p, bt, ln, ly: (b, h, 0, 0))
    # one (block_size, D) tile of one KV head of one physical page of one
    # layer: the layer and page dims are squeezed and the head is column
    # block h of the token rows, so the block's minor two dims are
    # (block_size, D) -- tile-aligned for the chip's (8, 128) layout
    kv_block = pl.BlockSpec((None, None, bs, D),
                            lambda b, h, p, bt, ln, ly: (ly[0], bt[b, p],
                                                         0, h))
    state_block = pl.BlockSpec((1, 1, G, 1),
                               lambda b, h, p, bt, ln, ly: (b, h, 0, 0))
    if return_state:
        out_shape = (jax.ShapeDtypeStruct((B, KH, G, D), jnp.float32),
                     jax.ShapeDtypeStruct((B, KH, G, 1), jnp.float32),
                     jax.ShapeDtypeStruct((B, KH, G, 1), jnp.float32))
        out_specs = (out_block, state_block, state_block)
    else:
        out_shape = jax.ShapeDtypeStruct((B, KH, G, D), q.dtype)
        out_specs = out_block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # block_tables, lengths, layer
        grid=(B, KH, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, p, bt, ln, ly: (b, h, 0, 0)),
            kv_block,
            kv_block,
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),     # running max m
            pltpu.VMEM((G, 1), jnp.float32),     # running sum l
            pltpu.VMEM((G, D), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention_decode",
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), qr, k_pages, v_pages)
    if return_state:
        acc, m, l = out
        return acc, m, l
    return out.reshape(B, H, D)
