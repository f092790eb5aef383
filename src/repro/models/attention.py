"""Attention: GQA projections, scan-based flash attention, split-KV decode.

Two compute paths (DESIGN.md §4):

  * ``flash_attention`` — train/prefill.  Online-softmax over KV chunks via
    ``lax.scan``; peak memory is O(S x chunk) per head instead of O(S^2).
    With the sequence-parallel recipe, Q stays sequence-sharded while K/V are
    gathered (the ``kv_seq`` logical axis), giving context parallelism that
    is agnostic to head counts.
  * ``decode_attention`` — single-token decode against a (possibly
    seq-sharded) KV cache; the softmax reductions over the sharded cache axis
    lower to XLA partial reductions + cross-replica combines (split-KV /
    flash-decoding on the mesh).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import layers

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def init_attention(key, cfg):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": layers.init_dense(kq, d, cfg.num_heads * hd, bias=cfg.qkv_bias),
        "wk": layers.init_dense(kk, d, cfg.num_kv_heads * hd, bias=cfg.qkv_bias),
        "wv": layers.init_dense(kv, d, cfg.num_kv_heads * hd, bias=cfg.qkv_bias),
        "wo": layers.init_dense(ko, cfg.num_heads * hd, d),
    }


def qkv_proj(params, x, cfg, mode):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = layers.dense(params["wq"], x, mode).reshape(B, S, cfg.num_heads, hd)
    k = layers.dense(params["wk"], x, mode).reshape(B, S, cfg.num_kv_heads, hd)
    v = layers.dense(params["wv"], x, mode).reshape(B, S, cfg.num_kv_heads, hd)
    return q, k, v


def flash_attention(q, k, v, *, causal: bool = True, q_offset=0,
                    chunk: int = 1024, kv_len: Optional[jax.Array] = None):
    """q (B,S,H,D); k/v (B,T,KH,D).  Returns (B,S,H,D).

    ``q_offset``: global position of q[0] (for chunked prefill continuation).
    ``kv_len``: optional valid-length mask over T (padded caches).
    """
    B, S, H, D = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk
    scale = D ** -0.5
    qr = (q.astype(jnp.float32) * scale).reshape(B, S, KH, G, D)

    def body(carry, idx):
        m, l, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k, idx * chunk, chunk, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, 1)
        s = jnp.einsum("bskgd,bckd->bskgc", qr, ks.astype(jnp.float32))
        kpos = idx * chunk + jnp.arange(chunk)
        mask = jnp.ones((S, chunk), bool)
        if causal:
            qpos = q_offset + jnp.arange(S)
            mask &= qpos[:, None] >= kpos[None, :]
        if kv_len is not None:
            mask &= kpos[None, :] < kv_len
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bskgc,bckd->bskgd", p, vs.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((B, S, KH, G), NEG_INF, jnp.float32),
            jnp.zeros((B, S, KH, G), jnp.float32),
            jnp.zeros((B, S, KH, G, D), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.reshape(B, S, H, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, k_scale=None,
                     v_scale=None):
    """q (B,S,H,D) against cache (B,T,KH,D).  Query row j sits at global
    position ``cache_len + j`` and attends to cache positions
    ``<= cache_len + j`` (its own K/V was already written there).  S = 1 is
    the classic one-token decode; S > 1 is the speculative multi-token
    verify step (the S rows form a tiny causal wedge over the cache).

    ``cache_len`` may be a scalar (whole batch at one position — static
    serving) or a (B,) vector of per-slot positions (continuous batching,
    where each slot decodes at its own depth).

    int8 KV cache support (per-token-per-head scales, EXACT factorization):
        score[b,kh,g,t] = (q . k_q[t]) * k_scale[b,t,kh]
        out = sum_t p[t] * v_scale[b,t,kh] * v_q[t]
    """
    B, S, H, D = q.shape
    T, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    scale = D ** -0.5
    qr = (q.astype(jnp.float32) * scale).reshape(B, S, KH, G, D)
    s = jnp.einsum("bskgd,btkd->bskgt", qr, k_cache.astype(jnp.float32))
    if k_scale is not None:
        s = s * jnp.transpose(k_scale, (0, 2, 1))[:, None, :, None, :]
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        lim = cache_len + jnp.arange(S)                       # (S,)
        valid = (jnp.arange(T)[None, :] <= lim[:, None])[None, :, None,
                                                         None, :]
    else:
        lim = cache_len[:, None] + jnp.arange(S)[None, :]     # (B, S)
        valid = (jnp.arange(T)[None, None, :]
                 <= lim[:, :, None])[:, :, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * jnp.transpose(v_scale, (0, 2, 1))[:, None, :, None, :]
    out = jnp.einsum("bskgt,btkd->bskgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


def decode_positions(cache_len, B, S: int = 1):
    """(B, S) RoPE positions for a decode/verify step: row j of slot b sits
    at ``cache_len[b] + j`` (scalar ``cache_len`` = whole batch at one
    depth)."""
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        return jnp.broadcast_to((cache_len + jnp.arange(S))[None], (B, S))
    return cache_len[:, None] + jnp.arange(S)[None, :]


def write_kv(cache, new, cache_len):
    """Write ``new`` (B, S, ...) into ``cache`` (B, T, ...) at positions
    ``cache_len .. cache_len + S - 1`` — scalar ``cache_len`` (one
    dynamic_update_slice for the whole batch) or (B,) vector (per-slot
    scatter, continuous batching / speculative verify).  Vector scatters
    whose positions fall outside T are dropped (jax OOB-scatter semantics):
    a speculative tail past the slab capacity lands nowhere and is never
    read back (the accept rule stops at the committed budget)."""
    cache_len = jnp.asarray(cache_len)
    S = new.shape[1]
    if cache_len.ndim == 0:
        if S == 1:
            idx = (0, cache_len) + (0,) * (cache.ndim - 2)
            return jax.lax.dynamic_update_slice(cache,
                                                new.astype(cache.dtype), idx)
        # multi-row: scatter per position so an overrunning tail DROPS
        # (dynamic_update_slice would clamp the start index and shift the
        # whole window backward over valid entries)
        pos = cache_len + jnp.arange(S)
        return cache.at[:, pos].set(new.astype(cache.dtype), mode="drop")
    B = new.shape[0]
    pos = cache_len[:, None] + jnp.arange(S)[None, :]
    return cache.at[jnp.arange(B)[:, None], pos].set(
        new.astype(cache.dtype), mode="drop")


def paged_write_kv(pages, new, page_ids, offsets):
    """Write ``new`` (B, S, KH[, D]) into the stacked token-major pages
    (L, N, bs, KH[*D]) at per-(sequence, row) (page, in-block offset)
    positions, both (B, S).  ``page_ids`` number the stack's pages
    layer-major: page ``layer * N + block`` is physical block ``block`` of
    layer ``layer``.  Each row is one contiguous token row of the pool
    itself, so the pool carried through the layer scan is updated in place
    (``tests/test_tpu_compile.py`` pins that no pool-sized temporary or
    copy appears).  Inactive rows and speculative overhang past a slot's
    block table target the layer's trash block (block 0) — written, never
    read."""
    N = pages.shape[1]
    ids = page_ids.reshape(-1)
    rows = new.reshape(ids.shape[0], pages.shape[-1]).astype(pages.dtype)
    return pages.at[ids // N, ids % N, offsets.reshape(-1)].set(rows)


def paged_verify_attention(q, k_pages, v_pages, block_tables, cache_len,
                           layer, *, k_scale=None, v_scale=None):
    """Multi-token verify attention over block-paged KV: gather each slot's
    pages of layer ``layer`` dense through its block table, then run the
    same causal-wedge masking as :func:`decode_attention`.  The Pallas
    decode kernel is a one-query-row program, so the S > 1 verify path
    always takes the XLA gather formulation (it partitions under GSPMD on a
    mesh, like the paged-attention oracle)."""
    from repro.kernels.paged_attention.ref import gather_kv, gather_pages
    KH = k_pages.shape[-1] // q.shape[3]

    def lin(p):
        return gather_pages(p, block_tables, layer)

    return decode_attention(
        q, gather_kv(k_pages, block_tables, layer, KH),
        gather_kv(v_pages, block_tables, layer, KH), cache_len,
        k_scale=lin(k_scale) if k_scale is not None else None,
        v_scale=lin(v_scale) if v_scale is not None else None)


def paged_decode_attention(q, k_pages, v_pages, block_tables, cache_len,
                           layer, *, k_scale=None, v_scale=None):
    """q (B,1,H,D) against layer ``layer`` of the stacked token-major pages
    (L, N, bs, KH * D) through per-slot block tables (B, P); positions
    <= cache_len valid, exactly as :func:`decode_attention`.  Dispatches to
    the Pallas paged-attention kernel / XLA gather oracle per the active
    matmul backend (under a mesh the kernel runs inside ``shard_map`` —
    ``kernels/paged_attention/ops.py``)."""
    from repro.kernels.paged_attention.ops import paged_attention
    out = paged_attention(q[:, 0], k_pages, v_pages, block_tables,
                          jnp.asarray(cache_len), layer,
                          k_scale_pages=k_scale, v_scale_pages=v_scale)
    return out[:, None]


def quantize_kv(k, v):
    """Per (batch, position, head) symmetric int8 quantization of K/V.

    k/v (B, S, KH, D) -> (k_q int8, k_scale f32 (B,S,KH), v_q, v_scale)."""
    def one(t):
        amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
        s = jnp.maximum(amax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(t.astype(jnp.float32) / s[..., None]),
                     -127, 127).astype(jnp.int8)
        return q, s
    kq, ks = one(k)
    vq, vs = one(v)
    return kq, ks, vq, vs


def attention_block(params, x, cfg, mode, *, cos, sin, causal=True,
                    cross_kv=None, cross_len=None):
    """Full attention sub-block for train/prefill (returns out, (k, v)).

    ``cross_kv``: (k, v) from an encoder — cross-attention (no RoPE on q? we
    follow standard enc-dec: RoPE is not applied for cross attention)."""
    B, S, _ = x.shape
    q, k, v = qkv_proj(params, x, cfg, mode)
    if cross_kv is not None:
        k, v = cross_kv
        out = flash_attention(q, k, v, causal=False, kv_len=cross_len)
    else:
        if cos is not None:
            q = layers.apply_rope(q, cos, sin)
            k = layers.apply_rope(k, cos, sin)
        k = shard(k, "batch", "kv_seq", "heads", None)
        v = shard(v, "batch", "kv_seq", "heads", None)
        out = flash_attention(q, k, v, causal=causal)
    out = out.reshape(B, S, cfg.num_heads * cfg.resolved_head_dim)
    return layers.dense(params["wo"], out, mode), (k, v)
