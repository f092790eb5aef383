"""Unified model API: family dispatch + dry-run input specs.

Every architecture exposes:
    init(key, cfg) -> params
    loss_fn(params, cfg, batch) -> (loss, metrics)          [train_step]
    prefill(params, cfg, batch, cache_T) -> (logits, cache) [prefill_step]
    decode_step(params, cfg, batch) -> (logits, cache)      [serve_step]

``input_specs(cfg, shape)`` returns jax.ShapeDtypeStruct pytrees for every
model input of that workload shape — the dry-run lowers against these, so no
host allocation ever happens for the full-size configs.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import causal_lm, encdec, rwkv_model, zamba_model
from repro.models.layers import DTYPE

_FAMILY_MODULES = {
    "dense": causal_lm,
    "moe": causal_lm,
    "vlm": causal_lm,
    "ssm": rwkv_model,
    "hybrid": zamba_model,
    "audio": encdec,
}


def module_for(cfg: ArchConfig):
    return _FAMILY_MODULES[cfg.family]


def init(key, cfg: ArchConfig):
    return module_for(cfg).init(key, cfg)


def loss_fn(params, cfg: ArchConfig, batch):
    return module_for(cfg).loss_fn(params, cfg, batch)


def prefill(params, cfg: ArchConfig, batch, cache_T: int, prompt_lens=None):
    """``prompt_lens`` (B,) enables ragged right-padded prompt batches for
    families whose prefill is position-independent of right padding
    (attention KV families); recurrent families (ssm/hybrid) integrate every
    token into their state and do not support it."""
    if prompt_lens is None:
        return module_for(cfg).prefill(params, cfg, batch, cache_T)
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            f"family {cfg.family!r} has recurrent state: right-padded "
            f"ragged prefill would corrupt it (use exact-length groups)")
    return module_for(cfg).prefill(params, cfg, batch, cache_T,
                                   prompt_lens=prompt_lens)


def decode_step(params, cfg: ArchConfig, batch):
    return module_for(cfg).decode_step(params, cfg, batch)


def decode_step_paged(params, cfg: ArchConfig, batch):
    """Block-paged decode (``batch`` carries ``block_tables`` + per-slot
    ``cache_len``); position-indexed KV families only."""
    mod = module_for(cfg)
    if not hasattr(mod, "decode_step_paged"):
        raise ValueError(
            f"family {cfg.family!r} has no paged decode path; "
            f"use the slab cache backend")
    return mod.decode_step_paged(params, cfg, batch)


def supports_verify(cfg: ArchConfig) -> bool:
    """Can this family run the speculative multi-token verify step?
    (Position-indexed KV that can be rewound on rejection — dense/moe/vlm;
    recurrent state integrates every token irreversibly.)"""
    return hasattr(module_for(cfg), "verify_step")


def _verify_module(cfg: ArchConfig, name: str):
    mod = module_for(cfg)
    if not hasattr(mod, name):
        raise ValueError(
            f"family {cfg.family!r} has no multi-token verify path: "
            f"speculative decoding needs position-indexed KV that can be "
            f"rewound on rejection (recurrent state integrates every token "
            f"irreversibly); serve with draft='none'")
    return getattr(mod, name)


def verify_step(params, cfg: ArchConfig, batch):
    """Speculative multi-token verify (slab cache): append the (B, S)
    tokens of ``batch`` at per-slot ``cache_len`` in one forward pass and
    return per-position logits (B, S, V) for greedy accept/reject.
    Position-indexed KV families only (dense/moe/vlm)."""
    return _verify_module(cfg, "verify_step")(params, cfg, batch)


def verify_step_paged(params, cfg: ArchConfig, batch):
    """Block-paged speculative verify (``batch`` carries ``block_tables``);
    see :func:`verify_step`."""
    return _verify_module(cfg, "verify_step_paged")(params, cfg, batch)


# ---------------------------------------------------------------------------
# Dry-run input specs (ShapeDtypeStruct stand-ins, shardable, no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(d) for d in shape), dtype)


def _tokens_spec(B, S):
    return _sds((B, S), jnp.int32)


def _vlm_extras(cfg, B, S):
    return {
        "vision_embeds": _sds((B, S, cfg.d_model), DTYPE),
        "vision_mask": _sds((B, S), jnp.bool_),
        "positions": _sds((3, B, S), jnp.int32),
    }


def cache_specs(cfg: ArchConfig, B: int, cache_T: int):
    """ShapeDtypeStruct pytree of the decode cache for this family."""
    hd = cfg.resolved_head_dim
    if cfg.family in ("dense", "moe", "vlm"):
        kv = (cfg.num_layers, B, cache_T, cfg.num_kv_heads, hd)
        if cfg.kv_cache_int8:
            sc = (cfg.num_layers, B, cache_T, cfg.num_kv_heads)
            return {"k": _sds(kv, jnp.int8), "k_scale": _sds(sc, jnp.float32),
                    "v": _sds(kv, jnp.int8), "v_scale": _sds(sc, jnp.float32)}
        return {"k": _sds(kv, DTYPE), "v": _sds(kv, DTYPE)}
    if cfg.family == "ssm":
        d = cfg.d_model
        n = cfg.rwkv_head_dim
        h = d // n
        L = cfg.num_layers
        return {"wkv": _sds((L, B, h, n, n), jnp.float32),
                "x_tm": _sds((L, B, d), DTYPE),
                "x_cm": _sds((L, B, d), DTYPE)}
    if cfg.family == "hybrid":
        from repro.models import mamba2
        n_sup = cfg.num_layers // cfg.attn_every
        di = mamba2.d_inner(cfg)
        conv_dim = di + 2 * cfg.ssm_state
        h = mamba2.n_ssm_heads(cfg)
        return {
            "conv": _sds((n_sup, cfg.attn_every, B, cfg.ssm_conv_width - 1,
                          conv_dim), DTYPE),
            "ssm": _sds((n_sup, cfg.attn_every, B, h, cfg.ssm_head_dim,
                         cfg.ssm_state), jnp.float32),
            "k": _sds((n_sup, B, cache_T, cfg.num_kv_heads, hd), DTYPE),
            "v": _sds((n_sup, B, cache_T, cfg.num_kv_heads, hd), DTYPE),
        }
    if cfg.family == "audio":
        L = cfg.num_layers
        src_T = max(cache_T // 4, 128)
        kv = (L, B, cache_T, cfg.num_kv_heads, hd)
        ckv = (L, B, src_T, cfg.num_kv_heads, hd)
        return {"k": _sds(kv, DTYPE), "v": _sds(kv, DTYPE),
                "cross_k": _sds(ckv, DTYPE), "cross_v": _sds(ckv, DTYPE)}
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Sharding specs (mesh-parallel serving): logical axes -> PartitionSpec
# ---------------------------------------------------------------------------

def cache_logical_axes(cfg: ArchConfig):
    """Pytree (same structure as ``cache_specs``) of logical-axis name
    tuples for every decode-cache leaf, resolvable against the
    ``distributed.sharding`` recipes.  The serving executor turns these into
    ``PartitionSpec``s (``cache_pspecs``) for device placement, and the
    decode step re-applies them as sharding constraints so the pooled cache
    keeps one resident layout across steps."""
    kv = (None, "batch", "cache_seq", "heads", None)
    sc = (None, "batch", "cache_seq", "heads")
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.kv_cache_int8:
            return {"k": kv, "k_scale": sc, "v": kv, "v_scale": sc}
        return {"k": kv, "v": kv}
    if cfg.family == "ssm":
        return {"wkv": (None, "batch", "heads", None, None),
                "x_tm": (None, "batch", None),
                "x_cm": (None, "batch", None)}
    if cfg.family == "hybrid":
        return {"conv": (None, None, "batch", None, None),
                "ssm": (None, None, "batch", "heads", None, None),
                "k": kv, "v": kv}
    if cfg.family == "audio":
        return {"k": kv, "v": kv, "cross_k": kv, "cross_v": kv}
    raise ValueError(cfg.family)


def paged_cache_logical_axes(cfg: ArchConfig):
    """Logical axes of the block-paged cache: every leaf fully replicated.
    The page pool has no batch/sequence axis to lay on a mesh — physical
    pages are gathered through block tables, which stay replicated too."""
    specs = paged_cache_specs(cfg, 2, 1)
    return jax.tree.map(lambda s: (None,) * len(s.shape), specs)


def cache_pspec_tree(cfg: ArchConfig, cache_like, mesh_axes,
                     recipe_name: str = "decode", *, paged: bool = False):
    """PartitionSpec pytree matching ``cache_like`` (concrete arrays or
    ShapeDtypeStructs), resolved from the logical-axis rules.  This is THE
    resolution — the mesh executor places caches with it and
    ``cache_pspecs``/``paged_cache_pspecs`` are shape-spec facades over
    it, so placement and the spec helpers cannot drift apart."""
    from repro.distributed import sharding as shd
    if not isinstance(mesh_axes, dict):
        mesh_axes = shd.mesh_axes_dict(mesh_axes)
    axes = (paged_cache_logical_axes(cfg) if paged
            else cache_logical_axes(cfg))
    return jax.tree.map(
        lambda l, la: shd.logical_pspec(l.shape, la, recipe_name, mesh_axes),
        cache_like, axes)


def cache_pspecs(cfg: ArchConfig, n_slots: int, cache_T: int, mesh_axes,
                 recipe_name: str = "decode"):
    """PartitionSpec pytree for the pooled decode cache of this family,
    resolved from the logical-axis rules (``decode``: slot/batch axis over
    "data", KV sequence axis over "model"; non-divisible dims stay
    replicated).  ``mesh_axes``: {axis name: size} or a concrete Mesh."""
    return cache_pspec_tree(cfg, cache_specs(cfg, n_slots, cache_T),
                            mesh_axes, recipe_name)


def paged_cache_pspecs(cfg: ArchConfig, num_blocks: int, block_size: int,
                       mesh_axes=None, recipe_name: str = "decode"):
    """PartitionSpec pytree for the block-paged cache: fully replicated
    (see ``paged_cache_logical_axes``)."""
    return cache_pspec_tree(cfg,
                            paged_cache_specs(cfg, num_blocks, block_size),
                            mesh_axes or {}, recipe_name, paged=True)


def param_pspecs(params, mesh_axes, recipe_name: str = "decode"):
    """PartitionSpec pytree for the model params under a serving recipe —
    weight-stationary TP: last dims over "model" (``decode``/``serve``), 2D
    FSDP x TP under ``train``.  Thin facade over
    ``distributed.sharding.param_specs`` so serving code only needs the
    model API surface."""
    from repro.distributed import sharding as shd
    return shd.param_specs(params, recipe_name, mesh_axes)


def shard_cache(cfg: ArchConfig, cache, *, paged: bool = False):
    """Re-apply the decode-cache sharding constraints to ``cache`` inside a
    trace (no-op without an active mesh/recipe).  The executor calls this on
    the cache a jitted step returns, pinning the output layout to the input
    layout so donated cache buffers alias instead of resharding."""
    from repro.distributed.sharding import shard
    axes = (paged_cache_logical_axes(cfg) if paged
            else cache_logical_axes(cfg))
    return jax.tree.map(lambda leaf, la: shard(leaf, *la), cache, axes)


# ---------------------------------------------------------------------------
# Block-paged decode caches (paged cache backend)
# ---------------------------------------------------------------------------

def paged_cache_specs(cfg: ArchConfig, num_blocks: int, block_size: int):
    """ShapeDtypeStruct pytree of the block-paged decode cache, laid out
    token-major: every KV leaf is (L, num_blocks, block_size,
    KV_heads * head_dim) and every int8 scale leaf (L, num_blocks,
    block_size, KV_heads).  One token's row of all its KV heads is
    contiguous, so the decode step writes it in place into the pool it
    carries through the layer scan; the paged-attention kernel reads one
    head's (block_size, head_dim) tile as a column block of a page, at the
    layer it is handed, straight from the stacked pool.  Position-indexed
    KV families only — recurrent state has no sequence axis to page."""
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"no paged cache layout for family {cfg.family!r}")
    hd = cfg.resolved_head_dim
    kv = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads * hd)
    if cfg.kv_cache_int8:
        sc = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads)
        return {"k": _sds(kv, jnp.int8), "k_scale": _sds(sc, jnp.float32),
                "v": _sds(kv, jnp.int8), "v_scale": _sds(sc, jnp.float32)}
    return {"k": _sds(kv, DTYPE), "v": _sds(kv, DTYPE)}


def zeros_paged_cache(cfg: ArchConfig, num_blocks: int, block_size: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_cache_specs(cfg, num_blocks, block_size))


def paged_insert(cfg: ArchConfig, pages, src_cache, block_ids, src_index=0):
    """Scatter request ``src_index`` of a prefill cache (padded to
    ``len(block_ids) * block_size`` positions) into physical pages.

    ``block_ids``: (P,) int32 — logical block i of the sequence lands in
    physical page ``block_ids[i]``.  Blocks that must NOT be written
    (prefix-sharing hits) are redirected to the trash page (id 0) by the
    caller; ``block_ids``/``src_index`` may be traced (one jit covers every
    admission of a given prefill batch shape)."""
    block_ids = jnp.asarray(block_ids, jnp.int32)

    def put(page, src):
        # src (L, B, T, KH[, D]) -> row (L, T, KH[, D])
        # -> token-major blocks (L, P, bs, KH[*D])
        row = jax.lax.dynamic_index_in_dim(src, src_index, axis=1,
                                           keepdims=False)
        blocked = row.reshape(page.shape[0], block_ids.shape[0],
                              *page.shape[2:])
        return page.at[:, block_ids].set(blocked.astype(page.dtype))

    return jax.tree.map(put, pages, src_cache)


# ---------------------------------------------------------------------------
# Slot-granular cache surgery (continuous-batching serving)
# ---------------------------------------------------------------------------

def cache_batch_axes(cfg: ArchConfig):
    """Pytree (same structure as ``cache_specs``) giving the slot/batch axis
    of every decode-cache leaf.  The hybrid family stacks mamba states as
    (n_super, attn_every, B, ...) so its batch axis differs per leaf."""
    if cfg.family == "hybrid":
        return {"conv": 2, "ssm": 2, "k": 1, "v": 1}
    return jax.tree.map(lambda _: 1, cache_specs(cfg, 1, 8))


def zeros_cache(cfg: ArchConfig, n_slots: int, cache_T: int):
    """Concrete all-zeros decode cache for an ``n_slots``-wide slot pool."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, n_slots, cache_T))


def slot_insert(cfg: ArchConfig, pool_cache, src_cache, slot, src_index=0):
    """Write request ``src_index`` of ``src_cache`` (a prefill cache of batch
    size >= 1, padded to the pool's cache_T) into slot ``slot`` of the pooled
    cache.  ``slot``/``src_index`` may be traced (one jit covers all slots)."""
    axes = cache_batch_axes(cfg)

    def put(pool, src, ax):
        row = jax.lax.dynamic_index_in_dim(src, src_index, axis=ax,
                                           keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            pool, row.astype(pool.dtype), slot, axis=ax)

    return jax.tree.map(put, pool_cache, src_cache, axes)


def slot_extract(cfg: ArchConfig, pool_cache, slot):
    """Pull slot ``slot`` out of the pooled cache as a batch-1 cache."""
    axes = cache_batch_axes(cfg)
    return jax.tree.map(
        lambda pool, ax: jax.lax.dynamic_slice_in_dim(pool, slot, 1, axis=ax),
        pool_cache, axes)


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Model-input ShapeDtypeStructs for one (arch x workload-shape) cell."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch = {"tokens": _tokens_spec(B, S)}
        if cfg.family == "vlm":
            batch.update(_vlm_extras(cfg, B, S))
        if cfg.family == "audio":
            batch["src_embeds"] = _sds((B, S // 4, cfg.d_model), DTYPE)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": _tokens_spec(B, S)}
        if cfg.family == "vlm":
            batch.update(_vlm_extras(cfg, B, S))
        if cfg.family == "audio":
            batch["src_embeds"] = _sds((B, S // 4, cfg.d_model), DTYPE)
        return batch
    if shape.kind == "decode":
        batch = {"tokens": _tokens_spec(B, 1),
                 "cache": cache_specs(cfg, B, S),
                 "cache_len": _sds((), jnp.int32)}
        if cfg.family == "vlm":
            pass  # decode positions derive from cache_len (text continuation)
        return batch
    raise ValueError(shape.kind)


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS: 6*N*D for training (N = params, active for MoE),
    2*N*D for single forward; decode counts one token + attention reads."""
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence + attention over the cache
    tokens = shape.global_batch
    attn_read = 0.0
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        hd = cfg.resolved_head_dim
        attn_read = (4.0 * cfg.num_layers * cfg.num_heads * hd
                     * shape.seq_len * tokens)
    if cfg.family == "hybrid":
        hd = cfg.resolved_head_dim
        n_sup = cfg.num_layers // cfg.attn_every
        attn_read = 4.0 * n_sup * cfg.num_heads * hd * shape.seq_len * tokens
    return 2.0 * n_active * tokens + attn_read
