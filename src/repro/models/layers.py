"""Shared model layers: norms, dense (BitParticle-backed), embeddings, RoPE.

All dense contractions route through ``repro.core.bp_matmul.dense_apply`` so
the BitParticle numerics mode (bf16 / qat / bp_exact / bp_approx) is a
per-config switch for every architecture (DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.bp_matmul import dense_apply

DTYPE = jnp.bfloat16


def truncated_normal(key, shape, stddev, dtype=DTYPE):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * stddev).astype(dtype)


# --- norms -----------------------------------------------------------------

def init_rmsnorm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(x.dtype)


def init_layernorm(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layer_norm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return out.astype(x.dtype)


# --- dense -----------------------------------------------------------------

def init_dense(key, d_in, d_out, bias=False, stddev=None):
    stddev = stddev if stddev is not None else d_in ** -0.5
    p = {"w": truncated_normal(key, (d_in, d_out), stddev)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense(params, x, mode="bf16"):
    w = params["w"]
    if w.dtype == jnp.int8:
        # pre-quantized serving weights (int8 in HBM — the paper's W8 storage)
        from repro.core import probe
        from repro.core.bp_matmul import quantized_matmul
        int_mode = mode if mode in ("bp_exact", "bp_approx") else "bp_exact"
        probe.record_activation(x)
        y = quantized_matmul(x, w, params["w_scale"], int_mode)
    else:
        y = dense_apply(x, w.astype(x.dtype), mode)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


@jax.jit
def _quantize_kernel(w):
    """(..., K, N) float -> int8 + per-output-channel scale (..., N).  One
    fused program per leaf: the f32 view of the weight is never
    materialized, and leaves quantize one at a time."""
    w32 = w.astype(jnp.float32)
    scale = quant.compute_scale(w32, axis=(w.ndim - 2,))
    return (quant.quantize(w32, scale),
            scale.reshape(w.shape[:-2] + (w.shape[-1],)))


def quantize_dense_params(params):
    """Convert every dense kernel ("w", ndim>=2, float) to int8 + per-channel
    scale for weight-resident serving.  Embedding tables (gather-consumed)
    and 1D params are untouched."""

    def rec(node):
        if isinstance(node, dict):
            node = {k: rec(v) for k, v in node.items()}
            w = node.get("w")
            if (w is not None and hasattr(w, "ndim") and w.ndim >= 2
                    and jnp.issubdtype(w.dtype, jnp.floating)):
                # per-output-channel scales; leading dims (scan-stacked
                # layers) keep their own scales: (..., K, N) -> (..., N)
                scale_shape = w.shape[:-2] + (w.shape[-1],)
                if isinstance(w, jax.ShapeDtypeStruct):
                    node["w"] = jax.ShapeDtypeStruct(w.shape, jnp.int8)
                    node["w_scale"] = jax.ShapeDtypeStruct(scale_shape,
                                                           jnp.float32)
                else:
                    node["w"], node["w_scale"] = _quantize_kernel(w)
            return node
        return node

    return rec(params)


# --- embeddings ------------------------------------------------------------

def init_embedding(key, vocab, d):
    return {"table": truncated_normal(key, (vocab, d), d ** -0.5)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x):
    """Logits against the (possibly tied) embedding table."""
    return jnp.einsum("...d,vd->...v", x, params["table"].astype(x.dtype))


# --- rotary position embeddings ---------------------------------------------

def rope_angles(positions, head_dim: int, theta: float):
    """positions (..., S) int -> cos/sin (..., S, head_dim//2) f32."""
    half = head_dim // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def mrope_angles(positions3, head_dim: int, theta: float,
                 sections: Tuple[int, ...]):
    """Qwen2-VL M-RoPE: positions3 (3, B, S); per-frequency-band section ids
    pick which of the (t, h, w) position rows drives that band."""
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    cos, sin = rope_angles(positions3, head_dim, theta)  # (3, B, S, half)
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.asarray(sections), total_repeat_length=half)
    onehot = jax.nn.one_hot(sec_id, len(sections), dtype=jnp.float32)  # (half, 3)
    cos = jnp.einsum("nbsh,hn->bsh", cos, onehot)
    sin = jnp.einsum("nbsh,hn->bsh", sin, onehot)
    return cos, sin


def apply_rope(x, cos, sin):
    """x (B, S, H, D); cos/sin (B, S, D/2) — rotate-half convention."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[..., None, :].astype(jnp.float32)
    s = sin[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1)
    return out.astype(x.dtype)


# --- feed-forward ----------------------------------------------------------

def init_ffn(key, d, d_ff, ffn_type: str):
    k1, k2, k3 = jax.random.split(key, 3)
    if ffn_type == "swiglu":
        return {"w_gate": init_dense(k1, d, d_ff),
                "w_up": init_dense(k2, d, d_ff),
                "w_down": init_dense(k3, d_ff, d)}
    return {"w_up": init_dense(k1, d, d_ff),
            "w_down": init_dense(k2, d_ff, d)}


def ffn(params, x, ffn_type: str, mode="bf16"):
    if ffn_type == "swiglu":
        g = dense(params["w_gate"], x, mode)
        u = dense(params["w_up"], x, mode)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        u = dense(params["w_up"], x, mode)
        h = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
    return dense(params["w_down"], h, mode)
