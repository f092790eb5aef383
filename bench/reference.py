"""Plain float32 reference of a dense GQA decoder (Qwen2: QKV bias,
SwiGLU, RMSNorm, rotate-half RoPE), and the comparison that decides
``correct``.

It imports nothing of the program.  Weights are the benchmark's own
(``weights.build`` from the same seed), dequantized to float32 one layer
at a time inside a scan, so a model whose float32 copy would not fit the
chip still runs.  Every product is float32 at ``Precision.HIGHEST``;
attention runs in blocks of query rows.

The number compared is the widest logit gap: for every served token, how
far its reference logit lies below the reference's largest logit at that
position.  Greedy decoding of the reference itself reads 0.  The control
(``bits=4``) is this same reference with the operands of every int8
contraction fake-quantized to int4 (weights per output channel,
activations per row), the precision below the configuration's int8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _fake_quant(x, bits: int, axis: int):
    lim = 2 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / lim
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -lim, lim) * s


def _mm(x, leaf, bits):
    w = leaf["w"].astype(jnp.float32) * leaf["w_scale"][None, :]
    if bits:
        x = _fake_quant(x, bits, axis=-1)
        w = _fake_quant(w, bits, axis=0)
    y = jnp.dot(x, w, precision=HIGHEST)
    if "b" in leaf:
        y = y + leaf["b"]
    return y


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, cos, sin):
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA attention, (S, H, D) x (S, KH, D), in query blocks."""
    S, H, D = q.shape
    KH = k.shape[1]
    G = H // KH
    qb = q.reshape(S // Q_BLOCK, Q_BLOCK, KH, G, D) * D ** -0.5
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HIGHEST)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(S // Q_BLOCK), qb))
    return out.reshape(S, H * D)


@functools.partial(jax.jit, static_argnames=("arch", "n_out", "bits"))
def logits_at(params, tokens, start, *, arch: tuple, n_out: int,
              bits: int = 0):
    """Logits (n_out, V) at positions ``start .. start + n_out - 1`` of the
    token row ``tokens`` (S,), S a multiple of ``Q_BLOCK``."""
    H, KH, hd, theta, eps, tied = arch
    S = tokens.shape[0]
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["attn_norm"]["scale"], eps)
        q = _rope(_mm(h, a["wq"], bits).reshape(S, H, hd), cos, sin)
        k = _rope(_mm(h, a["wk"], bits).reshape(S, KH, hd), cos, sin)
        v = _mm(h, a["wv"], bits).reshape(S, KH, hd)
        x = x + _mm(_attention(q, k, v), a["wo"], bits)
        h = _rms(x, lp["ffn_norm"]["scale"], eps)
        f = lp["ffn"]
        g = _mm(h, f["w_gate"], bits)
        u = _mm(h, f["w_up"], bits)
        return x + _mm(jax.nn.silu(g) * u, f["w_down"], bits), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    x = jax.lax.dynamic_slice_in_dim(x, start, n_out)
    if tied:
        table = params["embed"]["table"].astype(jnp.float32)
        return jnp.dot(x, table.T, precision=HIGHEST)
    return _mm(x, params["lm_head"], bits)


def arch_of(conf: dict) -> tuple:
    H = conf["num_attention_heads"]
    return (H, conf["num_key_value_heads"],
            conf.get("head_dim") or conf["hidden_size"] // H,
            float(conf["rope_theta"]), float(conf["rms_norm_eps"]),
            bool(conf["tie_word_embeddings"]))


def seq_len(max_prompt: int, max_output: int) -> int:
    n = max_prompt + max_output
    return -(-n // Q_BLOCK) * Q_BLOCK


def served_gaps(params, conf: dict, prompt, tokens, *, S: int, n_out: int,
                control_bits: int = 0):
    """Per served token, its gap below the reference's best logit; with
    ``control_bits`` also the gap of the token the control puts first at
    each of those positions.  Returns (gaps, control_gaps or None) as
    lists of floats."""
    import numpy as np
    n = len(tokens)
    row = np.zeros(S, np.int32)
    seq = list(prompt) + list(tokens)
    row[:len(seq)] = seq
    start = len(prompt) - 1
    arch = arch_of(conf)
    ref = logits_at(params, jnp.asarray(row), start, arch=arch, n_out=n_out)
    idx = jnp.asarray(np.asarray(tokens, np.int32))
    best = jnp.max(ref[:n], axis=-1)
    gaps = np.asarray(best - ref[jnp.arange(n), idx]).tolist()
    ctrl = None
    if control_bits:
        low = logits_at(params, jnp.asarray(row), start, arch=arch,
                        n_out=n_out, bits=control_bits)
        pick = jnp.argmax(low[:n], axis=-1)
        ctrl = np.asarray(best - ref[jnp.arange(n), pick]).tolist()
    return gaps, ctrl
