"""Operations and bytes the model's contractions need, from config shapes.

These count the work the model asks for, not how a kernel tiles, pads or
splits it: ``2*M*K*N`` integer operations per int8 contraction of M rows,
and the bytes of its int8 operands, f32 scales and f32 output.  A change
of kernel implementation cannot move them.
"""

from __future__ import annotations

from typing import List, Tuple


def _dims(conf: dict):
    d, f = conf["hidden_size"], conf["intermediate_size"]
    H, KH = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // H
    V = -(-int(conf["vocab_size"]) // 256) * 256
    return d, f, H, KH, hd, V


def layer_matmuls(conf: dict) -> List[Tuple[int, int]]:
    """(K, N) of every int8 contraction in one decoder layer."""
    d, f, H, KH, hd, _ = _dims(conf)
    return [(d, H * hd), (d, KH * hd), (d, KH * hd), (H * hd, d),
            (d, f), (d, f), (f, d)]


def head_matmul(conf: dict) -> Tuple[int, int]:
    d, *_, V = _dims(conf)
    return d, V


def matmul_ops_per_token(conf: dict, with_head: bool) -> float:
    """Model operations of one token through every linear contraction
    (the int8 layers, and the output head when ``with_head``)."""
    L = conf["num_hidden_layers"]
    ops = L * sum(2 * k * n for k, n in layer_matmuls(conf))
    if with_head:
        k, n = head_matmul(conf)
        ops += 2 * k * n
    return float(ops)


def int8_contractions(conf: dict, rows: int):
    """(ops, bytes) of each int8 contraction one step makes over ``rows``
    rows: every layer's projections, and the output head when it is int8
    (untied)."""
    L = conf["num_hidden_layers"]
    shapes = layer_matmuls(conf) * L
    if not conf["tie_word_embeddings"]:
        shapes = shapes + [head_matmul(conf)]
    out = []
    for k, n in shapes:
        ops = 2.0 * rows * k * n
        byts = k * n + 4 * n + rows * k + 4 * rows + 4 * rows * n
        out.append((ops, float(byts)))
    return out


def roofline_s(work, ops_peak: float, bytes_per_s: float) -> float:
    """Least time the chip could take for a list of (ops, bytes) calls,
    each bound by whichever of compute or memory is slower."""
    return sum(max(o / ops_peak, b / bytes_per_s) for o, b in work)


def kv_bytes_per_token(conf: dict, kv_itemsize: int = 2) -> int:
    """Bytes of K and V one token keeps in every layer's cache."""
    _, _, _, KH, hd, _ = _dims(conf)
    return 2 * conf["num_hidden_layers"] * KH * hd * kv_itemsize


def attention_decode_work(conf: dict, ctx_tokens: int, rows: int,
                          kv_itemsize: int = 2):
    """(ops, bytes) of one decode step's paged attention over all layers:
    every cached K/V byte read once, the query and output rows."""
    d, _, H, KH, hd, _ = _dims(conf)
    L = conf["num_hidden_layers"]
    ops = 4.0 * ctx_tokens * H * hd * L
    byts = ctx_tokens * kv_bytes_per_token(conf, kv_itemsize) \
        + L * rows * 2 * H * hd * kv_itemsize
    return ops, float(byts)
