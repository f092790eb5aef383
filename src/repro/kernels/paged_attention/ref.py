"""XLA gather oracle for the paged-attention decode kernel.

Gathers each sequence's pages into a dense (B, T, KH, D) cache view and runs
the exact arithmetic of ``models/attention.py::decode_attention`` (same
einsum forms, same masking, same f32 softmax) — so it doubles as the proof
that block paging is a pure *storage* transform: on identical page contents
the oracle's output is the slab path's output.

This is also the CPU fallback behind the backend dispatch (and the path
taken when int8 KV scale pages are present — the Pallas kernel handles
float pages only).  It materializes the gathered cache copy per step; the
kernel exists to avoid exactly that HBM traffic on TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def gather_pages(pages, block_tables, layer=0):
    """Stacked token-major pages (L, N, bs, F) + (B, P) tables -> layer
    ``layer``'s (B, P*bs, F) dense view (F = KH * D for K/V, KH for int8
    scales).  One gather indexes the layer and the pages together, so no
    per-layer slice of the pool is made."""
    g = pages[layer, block_tables]                # (B, P, bs, F)
    B, P, bs = g.shape[:3]
    return g.reshape(B, P * bs, *g.shape[3:])


def gather_kv(pages, block_tables, layer, kv_heads):
    """:func:`gather_pages` of K/V pages split back into heads: the slab
    cache layout (B, T, KH, D)."""
    g = gather_pages(pages, block_tables, layer)
    return g.reshape(*g.shape[:2], kv_heads, -1)


def paged_attention_xla(q, k_pages, v_pages, block_tables, lengths, layer=0,
                        *, k_scale_pages=None, v_scale_pages=None):
    """q: (B, H, D); k_pages/v_pages: (L, N, bs, KH * D); block_tables:
    (B, P); lengths: (B,) last valid position (inclusive); layer: the layer
    whose pages are read.  Optional int8-KV scale pages: (L, N, bs, KH).
    Returns (B, H, D) in q.dtype."""
    B, H, D = q.shape
    KH = k_pages.shape[-1] // D
    G = H // KH
    k = gather_kv(k_pages, block_tables, layer, KH)     # (B, T, KH, D)
    v = gather_kv(v_pages, block_tables, layer, KH)
    T = k.shape[1]
    scale = D ** -0.5
    qr = (q.astype(jnp.float32) * scale).reshape(B, KH, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qr, k.astype(jnp.float32))
    if k_scale_pages is not None:
        ks = gather_pages(k_scale_pages, block_tables, layer)   # (B, T, KH)
        s = s * jnp.transpose(ks, (0, 2, 1))[:, :, None, :]
    valid = (jnp.arange(T)[None, :] <= lengths[:, None])[:, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale_pages is not None:
        vs = gather_pages(v_scale_pages, block_tables, layer)
        p = p * jnp.transpose(vs, (0, 2, 1))[:, :, None, :]
    out = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)
