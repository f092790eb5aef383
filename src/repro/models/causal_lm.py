"""Decoder-only causal LM covering the dense / GQA / MoE / VLM families.

Layer stack is a ``lax.scan`` over stacked layer params (compact HLO — a
512-device SPMD compile sees one layer body) with activation checkpointing.
The BitParticle matmul mode is plumbed through every dense contraction.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import probe as _probe
from repro.distributed.sharding import shard
from repro.models import attention, layers
from repro.models.moe import init_moe, moe_ffn


def init_layer(key, cfg):
    ka, kf, kn1, kn2 = jax.random.split(key, 4)
    p = {
        "attn_norm": layers.init_rmsnorm(cfg.d_model),
        "attn": attention.init_attention(ka, cfg),
        "ffn_norm": layers.init_rmsnorm(cfg.d_model),
    }
    if cfg.num_experts:
        p["moe"] = init_moe(kf, cfg)
    else:
        p["ffn"] = layers.init_ffn(kf, cfg.d_model, cfg.d_ff, cfg.ffn_type)
    return p


def init(key, cfg):
    ke, kl, kh = jax.random.split(key, 3)
    params = {
        "embed": layers.init_embedding(ke, cfg.vocab_padded, cfg.d_model),
        "layers": jax.vmap(lambda k: init_layer(k, cfg))(
            jax.random.split(kl, cfg.num_layers)),
        "final_norm": layers.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.init_dense(kh, cfg.d_model,
                                              cfg.vocab_padded)
    return params


def _angles(cfg, positions):
    """positions: (B, S) or (3, B, S) for M-RoPE."""
    hd = cfg.resolved_head_dim
    if cfg.mrope_sections:
        assert positions.ndim == 3
        return layers.mrope_angles(positions, hd, cfg.rope_theta,
                                   cfg.mrope_sections)
    return layers.rope_angles(positions, hd, cfg.rope_theta)


def _embed_inputs(params, cfg, batch):
    tokens = batch["tokens"]
    x = layers.embed(params["embed"], tokens)
    if cfg.family == "vlm" and "vision_embeds" in batch:
        x = jnp.where(batch["vision_mask"][..., None],
                      batch["vision_embeds"].astype(x.dtype), x)
    B, S = tokens.shape
    if "positions" in batch:
        positions = batch["positions"]
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(positions[None], (3, B, S))
    return x, positions


def _block(lp, x, cfg, mode, cos, sin):
    h = layers.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
    attn_out, kv = attention.attention_block(lp["attn"], h, cfg, mode,
                                             cos=cos, sin=sin)
    x = x + attn_out
    h = layers.rms_norm(lp["ffn_norm"], x, cfg.norm_eps)
    if cfg.num_experts:
        f, aux = moe_ffn(lp["moe"], h, cfg, mode)
    else:
        f, aux = layers.ffn(lp["ffn"], h, cfg.ffn_type, mode), jnp.float32(0)
    x = x + f
    x = shard(x, "batch", "seq", None)
    return x, kv, aux


def forward(params, cfg, batch, *, return_cache: bool = False,
            cache_T: Optional[int] = None):
    """Returns (hidden (B,S,D), aux_loss, cache|None)."""
    mode = cfg.matmul_mode
    probing = _probe.tap_active()
    x, positions = _embed_inputs(params, cfg, batch)
    x = shard(x, "batch", "seq", None)
    cos, sin = _angles(cfg, positions)
    # pre-scan taps (e.g. the VLM projector) must not become per-layer
    # closure constants of the scan body
    _probe.absorb_pending()

    def body(carry, lp):
        y, kv, aux = _block(lp, carry, cfg, mode, cos, sin)
        if return_cache:
            k, v = kv
            if cfg.kv_cache_int8:
                k, ks_, v, vs_ = attention.quantize_kv(k, v)
            if cache_T is not None and cache_T > k.shape[1]:
                pad_t = cache_T - k.shape[1]
                pad = [(0, 0), (0, pad_t), (0, 0), (0, 0)]
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
                if cfg.kv_cache_int8:
                    spad = [(0, 0), (0, pad_t), (0, 0)]
                    ks_, vs_ = jnp.pad(ks_, spad), jnp.pad(vs_, spad)
            k = shard(k, "batch", "cache_seq", "heads", None)
            v = shard(v, "batch", "cache_seq", "heads", None)
            if cfg.kv_cache_int8:
                ys = (k, ks_, v, vs_, aux)
            else:
                ys = (k, v, aux)
        else:
            ys = (aux,)
        if probing:
            ys = ys + (_probe.drain_layer(),)
        return y, ys

    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)
    if return_cache:
        if cfg.kv_cache_int8:
            x, ys = jax.lax.scan(body, x, params["layers"])
            ks, kss, vs, vss, auxs = ys[:5]
            cache = {"k": ks, "k_scale": kss, "v": vs, "v_scale": vss}
        else:
            x, ys = jax.lax.scan(body, x, params["layers"])
            ks, vs, auxs = ys[:3]
            cache = {"k": ks, "v": vs}
    else:
        x, ys = jax.lax.scan(body, x, params["layers"])
        auxs = ys[0]
        cache = None
    if probing:
        _probe.emit_layers(ys[-1])
    x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x, jnp.sum(auxs), cache


def logits_from_hidden(params, cfg, x):
    if cfg.tie_embeddings:
        return layers.unembed(params["embed"], x)
    return layers.dense(params["lm_head"], x, cfg.matmul_mode)


def loss_fn(params, cfg, batch):
    """Causal LM loss (next-token prediction; final position masked)."""
    x, aux, _ = forward(params, cfg, batch)
    tokens = batch["tokens"]
    B, S = tokens.shape
    T = B * S
    x2 = shard(x.reshape(T, -1), "tokens_flat", None)
    logits = logits_from_hidden(params, cfg, x2).astype(jnp.float32)
    logits = shard(logits, "tokens_flat", None)
    # mask padded vocab region out of the softmax
    vmask = jnp.arange(cfg.vocab_padded) < cfg.vocab_size
    logits = jnp.where(vmask[None, :], logits, -1e9)
    targets = jnp.roll(tokens, -1, axis=1).reshape(T)
    valid = jnp.ones((B, S), bool).at[:, -1].set(False)
    if "loss_mask" in batch:
        valid &= batch["loss_mask"]
    valid = valid.reshape(T)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    nll = (lse - tgt) * valid
    loss = nll.sum() / jnp.maximum(valid.sum(), 1)
    metrics = {"ce_loss": loss, "aux_loss": aux,
               "valid_tokens": valid.sum()}
    return loss + 0.01 * aux, metrics


def prefill(params, cfg, batch, cache_T: int, prompt_lens=None):
    """Run the prompt, return (last-position logits, KV cache padded to
    cache_T).

    ``prompt_lens`` (B,) enables ragged right-padded batches (the
    scheduler's power-of-two prefill buckets): logits are gathered at each
    row's own last valid position.  Causal masking makes valid positions
    independent of the right padding, and padded cache positions sit beyond
    ``cache_len`` — masked in decode until overwritten."""
    x, _, cache = forward(params, cfg, batch, return_cache=True,
                          cache_T=cache_T)
    if prompt_lens is None:
        last = x[:, -1:, :]
    else:
        idx = (jnp.asarray(prompt_lens, jnp.int32) - 1)[:, None, None]
        last = jnp.take_along_axis(x, idx, axis=1)
    logits = logits_from_hidden(params, cfg, last)[:, 0]
    return logits, cache


def _decode_common(params, cfg, batch, *, write_fn, attend_fn):
    """Shared decode/verify body over S >= 1 appended tokens; the cache
    layout enters only through ``write_fn(cache_leaf, layer, new)`` (install
    the new tokens' K/V/scales) and ``attend_fn(q, cache, layer)``
    (attention over that layout).  Returns (logits (B, S, V), cache).

    The slab cache is scanned per layer (``xs``) and restacked (``ys``).
    The block-paged pool (the batch carries ``block_tables``) rides in the
    scan's carry whole, indexed by the layer: new rows are written into it
    in place and the kernel reads its layer where it lies, so the step
    makes no per-layer slice or stacked copy of the pool."""
    mode = cfg.matmul_mode
    probing = _probe.tap_active()
    tokens, cache = batch["tokens"], batch["cache"]
    cache_len = jnp.asarray(batch["cache_len"])
    B, S = tokens.shape
    x = layers.embed(params["embed"], tokens)
    x = shard(x, "batch", None, None)
    _probe.absorb_pending()
    pos = attention.decode_positions(cache_len, B, S)
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    cos, sin = _angles(cfg, pos)
    hd = cfg.resolved_head_dim

    def layer_step(x, lp, kv, li):
        """One layer: ``kv`` holds this layer's slab leaves, or the whole
        paged pool with ``li`` naming the layer."""
        h = layers.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
        q, k, v = attention.qkv_proj(lp["attn"], h, cfg, mode)
        q = layers.apply_rope(q, cos, sin)
        k = layers.apply_rope(k, cos, sin)
        if cfg.kv_cache_int8:
            k, ks_, v, vs_ = attention.quantize_kv(k, v)
            new = {"k": k, "k_scale": ks_, "v": v, "v_scale": vs_}
        else:
            new = {"k": k, "v": v}
        kv = {name: write_fn(kv[name], li, new[name]) for name in kv}
        out = attend_fn(q, kv, li)
        out = out.reshape(B, S, cfg.num_heads * hd)
        x = x + layers.dense(lp["attn"]["wo"], out, mode)
        h = layers.rms_norm(lp["ffn_norm"], x, cfg.norm_eps)
        if cfg.num_experts:
            f, _ = moe_ffn(lp["moe"], h, cfg, mode)
        else:
            f = layers.ffn(lp["ffn"], h, cfg.ffn_type, mode)
        return x + f, kv

    def taps():
        return (_probe.drain_layer(),) if probing else ()

    if "block_tables" in batch:
        def body(carry, layer_in):
            x, pool = carry
            lp, li = layer_in
            x, pool = layer_step(x, lp, pool, li)
            return (x, pool), taps()

        (x, new_cache), ys = jax.lax.scan(
            body, (x, cache),
            (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    else:
        def body(x, layer_in):
            lp, kv = layer_in
            x, kv = layer_step(x, lp, kv, None)
            return x, (kv,) + taps()

        x, ys = jax.lax.scan(body, x, (params["layers"], cache))
        new_cache, ys = ys[0], ys[1:]
    if probing:
        _probe.emit_layers(ys[-1])
    x = layers.rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_from_hidden(params, cfg, x)
    return logits, new_cache


def _slab_fns(batch):
    """(write_fn, attend_fn) over the slab cache layout for the S tokens of
    ``batch`` (S = 1: decode; S > 1: speculative verify)."""
    cache_len = jnp.asarray(batch["cache_len"])

    def write_fn(c, _layer, new):
        c = attention.write_kv(c, new, cache_len)
        if c.ndim == 4:   # KV leaves (B, T, KH, hd)
            c = shard(c, "batch", "cache_seq", "heads", None)
        else:             # int8 KV scale leaves (B, T, KH): same layout, so
            c = shard(c, "batch", "cache_seq", "heads")
        # the resident cache keeps ONE mesh placement across decode steps
        # (the executor donates the buffer — layout drift would force a
        # reshard copy instead of aliasing)
        return c

    def attend_fn(q, kv, _layer):
        return attention.decode_attention(
            q, kv["k"], kv["v"], cache_len,
            k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"))

    return write_fn, attend_fn


def _paged_fns(batch):
    """(write_fn, attend_fn) over the block-paged layout.  Write targets
    past a slot's table span are redirected to the trash block (speculative
    overhang lands nowhere); the S = 1 attend dispatches to the Pallas
    kernel / XLA oracle, S > 1 takes the dense-gather verify formulation."""
    cache_len = jnp.asarray(batch["cache_len"])
    tables = jnp.asarray(batch["block_tables"], jnp.int32)
    bs = batch["cache"]["k"].shape[2]          # (L, N, bs, KH * D)
    S = batch["tokens"].shape[1]
    P = tables.shape[1]
    # physical write target per (slot, row): table entry at pos // bs
    pos = cache_len[:, None] + jnp.arange(S)[None, :]
    bi = pos // bs
    blk = jnp.take_along_axis(tables, jnp.minimum(bi, P - 1), axis=1)
    blk = jnp.where(bi < P, blk, 0)      # overhang -> trash block
    off = pos % bs

    def write_fn(pool, layer, new):
        return attention.paged_write_kv(pool, new,
                                        layer * pool.shape[1] + blk, off)

    def attend_fn(q, pool, layer):
        attend = (attention.paged_decode_attention if S == 1
                  else attention.paged_verify_attention)
        return attend(q, pool["k"], pool["v"], tables, cache_len, layer,
                      k_scale=pool.get("k_scale"),
                      v_scale=pool.get("v_scale"))

    return write_fn, attend_fn


def decode_step(params, cfg, batch):
    """One-token decode.  batch: tokens (B,1), cache {k,v}: (L,B,T,KH,Dh),
    cache_len: scalar int32 (whole batch at one depth) or (B,) int32
    (per-slot depths, continuous batching).  Returns (logits (B,V), cache)."""
    write_fn, attend_fn = _slab_fns(batch)
    logits, cache = _decode_common(params, cfg, batch,
                                   write_fn=write_fn, attend_fn=attend_fn)
    return logits[:, 0], cache


def verify_step(params, cfg, batch):
    """Speculative multi-token verify against the slab cache.

    batch: tokens (B, S) — the last committed token followed by S-1 draft
    tokens per slot, appended in ONE forward pass at per-slot positions
    ``cache_len .. cache_len + S - 1`` (row j attends causally through the
    cache up to its own position).  Returns (logits (B, S, V), cache):
    ``logits[:, j]`` is the target distribution AFTER consuming fed token
    j — greedy accept compares ``argmax(logits[:, j-1])`` with draft j.
    Rows past a slot's real draft length are padding: their K/V land beyond
    the committed region (masked, rolled back by the cache manager)."""
    write_fn, attend_fn = _slab_fns(batch)
    return _decode_common(params, cfg, batch,
                          write_fn=write_fn, attend_fn=attend_fn)


def decode_step_paged(params, cfg, batch):
    """One-token decode against a block-paged KV cache.

    batch: tokens (B,1); cache {k,v[,k_scale,v_scale]} with KV paged
    token-major as (L, num_blocks, block_size, KH * Dh); block_tables (B, P)
    int32 physical page ids; cache_len (B,) int32 per-slot positions.  The
    new token's K/V row is written in place at (layer, table[pos // bs],
    pos % bs) per slot, and attention reads the layer's pages through the
    block table (Pallas kernel / XLA oracle per the active backend).  The
    page pool has no batch/cache_seq axes to lay on the mesh, so paged
    leaves stay replicated.  Returns (logits, cache)."""
    write_fn, attend_fn = _paged_fns(batch)
    logits, cache = _decode_common(params, cfg, batch,
                                   write_fn=write_fn, attend_fn=attend_fn)
    return logits[:, 0], cache


def verify_step_paged(params, cfg, batch):
    """Speculative multi-token verify against the block-paged cache — the
    :func:`verify_step` contract with ``block_tables`` routing the writes
    and the dense-gather verify attention.  The cache manager must have
    prepared writable blocks for each slot's committed span
    (``prepare_append`` allocates/CoWs); overhang rows write to the trash
    block."""
    write_fn, attend_fn = _paged_fns(batch)
    return _decode_common(params, cfg, batch,
                          write_fn=write_fn, attend_fn=attend_fn)
