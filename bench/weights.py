"""Random weights made on the device from the seed, in the served form.

One jitted call builds the tree that ``quantize_dense_params`` would give
for ``api.init``: int8 kernels with per-output-channel f32 scales, a bf16
embedding, f32 norms and biases.  No float copy of a kernel is ever made,
so a model whose int8 weights fill half the chip still fits.  The values
are the benchmark's, drawn here; the reference draws the same ones with
the same function.

Nothing here imports the program: the harness checks that ``layout``
matches the tree the program expects before it serves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INT8_STD = 73.6     # std of integers uniform on [-127, 127]


def key_from_seed(seed: int):
    """A PRNG key from a seed of any size (PRNGKey keeps only 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def padded_vocab(conf: dict) -> int:
    """Rows of the embedding as the program holds them: the vocabulary
    rounded up to a multiple of 256."""
    return -(-int(conf["vocab_size"]) // 256) * 256


def layout(conf: dict):
    """Shape/dtype tree of the served parameters of a dense GQA decoder
    (SwiGLU, RMSNorm; a bias on the Q, K and V projections where the
    configuration's ``serving.qkv_bias`` is true, as in Qwen2), from the
    configuration file alone."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    L, H, KH = (conf["num_hidden_layers"], conf["num_attention_heads"],
                conf["num_key_value_heads"])
    hd = conf.get("head_dim") or d // H
    V = padded_vocab(conf)
    s = jax.ShapeDtypeStruct
    qkv_bias = bool(conf["serving"]["qkv_bias"])

    def dense(k, n, bias=False):
        leaf = {"w": s((L, k, n), jnp.int8), "w_scale": s((L, n), jnp.float32)}
        if bias:
            leaf["b"] = s((L, n), jnp.float32)
        return leaf

    tree = {
        "embed": {"table": s((V, d), jnp.bfloat16)},
        "layers": {
            "attn_norm": {"scale": s((L, d), jnp.float32)},
            "attn": {"wq": dense(d, H * hd, qkv_bias),
                     "wk": dense(d, KH * hd, qkv_bias),
                     "wv": dense(d, KH * hd, qkv_bias),
                     "wo": dense(H * hd, d)},
            "ffn_norm": {"scale": s((L, d), jnp.float32)},
            "ffn": {"w_gate": dense(d, f), "w_up": dense(d, f),
                    "w_down": dense(f, d)},
        },
        "final_norm": {"scale": s((d,), jnp.float32)},
    }
    if not conf["tie_word_embeddings"]:
        tree["lm_head"] = {"w": s((d, V), jnp.int8),
                           "w_scale": s((V,), jnp.float32)}
    return tree


def _leaf(key, path, sds, tree_at, draw=None):
    name = path[-1]
    if sds.dtype == jnp.int8:
        bits = jax.random.bits(key, sds.shape, jnp.uint8)
        return jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8),
                           jnp.int8(-127))
    if name == "w_scale":
        k_in = tree_at(path[:-1] + ("w",)).shape[-2]
        u = jax.random.uniform(key, sds.shape, jnp.float32, 0.75, 1.25)
        return u * (k_in ** -0.5 / INT8_STD)
    if name == "table":
        d = sds.shape[-1]
        return (jax.random.normal(key, sds.shape, jnp.float32)
                * d ** -0.5).astype(sds.dtype)
    if name == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, sds.shape, sds.dtype)
    if name == "b":
        return 0.02 * jax.random.normal(key, sds.shape, sds.dtype)
    if draw is not None:
        return draw(key, path, sds, tree_at)
    raise ValueError(f"no rule to draw parameter {'/'.join(path)} "
                     f"{sds.shape} {sds.dtype}")


def build(conf: dict, seed: int, shardings=None, *, shapes=None, draw=None):
    """The served parameter tree of ``shapes`` (default ``layout(conf)``),
    drawn by one jitted call straight into ``shardings`` (a tree like
    ``shapes``; None: the default device).  With JAX's partitionable
    threefry the values do not depend on the shardings.  ``draw(key, path,
    shape_dtype, tree_at)`` draws a leaf whose name the rules here do not
    know."""
    if shapes is None:
        shapes = layout(conf)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(getattr(p, "key", str(p)) for p in kp) for kp, _ in flat]
    by_path = dict(zip(paths, (s for _, s in flat)))

    def make(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_leaf(k, p, s, by_path.__getitem__, draw)
                  for k, p, (_, s) in zip(keys, paths, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    params = jax.jit(make, out_shardings=shardings)(key_from_seed(seed))
    jax.block_until_ready(params)
    return params
