"""Pallas paged-attention decode kernel vs the XLA gather oracle (interpret
mode) on ragged shapes and at every layer of a stacked token-major pool,
plus the block-paging storage-transform identity: paged attention over
scattered pages must equal dense ``decode_attention`` over the contiguous
cache it represents."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import bp_matmul
from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import (gather_kv, gather_pages,
                                              paged_attention_xla)
from repro.models.attention import decode_attention

jax.config.update("jax_default_matmul_precision", "float32")


def _case(seed, B, H, KH, D, n_blocks, bs, pages_per_seq, T_hi, L=1):
    """Random stacked token-major pages (L, n_blocks, bs, KH * D) + a
    random block table/lengths per sequence (unused table entries point at
    the trash page 0, whose random contents must stay masked)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (L, n_blocks, bs, KH * D), jnp.float32)
    vp = jax.random.normal(ks[2], (L, n_blocks, bs, KH * D), jnp.float32)
    rng = np.random.default_rng(seed)
    bt = np.zeros((B, pages_per_seq), np.int32)
    lengths = np.zeros(B, np.int32)
    for b in range(B):
        lengths[b] = rng.integers(0, T_hi)
        n_used = lengths[b] // bs + 1
        bt[b, :n_used] = rng.choice(
            np.arange(1, n_blocks), size=n_used, replace=False)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths)


RAGGED = [
    # B, H, KH, D, n_blocks, bs, pages_per_seq, T_hi
    (1, 2, 1, 8, 6, 4, 4, 16),
    (3, 4, 2, 16, 12, 4, 5, 20),
    (5, 6, 3, 32, 20, 8, 3, 24),
    (2, 8, 8, 16, 10, 2, 7, 14),     # MHA (G = 1), tiny blocks
    (4, 4, 1, 64, 16, 16, 2, 32),    # MQA-style, one kv head
]


@pytest.mark.parametrize("B,H,KH,D,n_blocks,bs,pps,T_hi", RAGGED)
def test_kernel_matches_xla_oracle(B, H, KH, D, n_blocks, bs, pps, T_hi):
    q, kp, vp, bt, lens = _case(hash((B, H, KH, D)) % 2**31, B, H, KH, D,
                                n_blocks, bs, pps, T_hi)
    want = paged_attention_xla(q, kp, vp, bt, lens)
    got = paged_attention_kernel(q, kp, vp, bt, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_oracle_equals_dense_decode_attention():
    """Block paging is a pure storage transform: gathering the pages of a
    sequence and running the slab ``decode_attention`` must give the same
    output as paged attention over the scattered pages.  The two programs
    differ in shape (the oracle drops the query-row axis), so XLA may
    reassociate the f32 softmax sums: equal to f32 rounding, not bitwise."""
    B, H, KH, D, n_blocks, bs, pps = 3, 4, 2, 16, 14, 4, 5
    q, kp, vp, bt, lens = _case(11, B, H, KH, D, n_blocks, bs, pps, 18)
    paged = paged_attention_xla(q, kp, vp, bt, lens)
    k_dense = gather_kv(kp, bt, 0, KH)     # (B, pps*bs, KH, D)
    v_dense = gather_kv(vp, bt, 0, KH)
    dense = decode_attention(q[:, None], k_dense, v_dense, lens)[:, 0]
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=1e-6, atol=1e-6)


def test_int8_scale_pages_route_and_match():
    """int8 KV scale pages take the XLA path in every backend and apply the
    exact per-token-per-head scale factorization of ``decode_attention``."""
    B, H, KH, D, n_blocks, bs, pps = 2, 4, 2, 16, 10, 4, 4
    q, kp, vp, bt, lens = _case(5, B, H, KH, D, n_blocks, bs, pps, 14)
    kq = jnp.round(jnp.clip(kp * 20, -127, 127)).astype(jnp.int8)
    vq = jnp.round(jnp.clip(vp * 20, -127, 127)).astype(jnp.int8)
    ks = jnp.abs(jax.random.normal(jax.random.PRNGKey(3),
                                   (1, n_blocks, bs, KH))) + 0.01
    vs = jnp.abs(jax.random.normal(jax.random.PRNGKey(4),
                                   (1, n_blocks, bs, KH))) + 0.01
    with bp_matmul.use_matmul_backend("kernel_interpret"):
        got = paged_attention(q, kq, vq, bt, lens,
                              k_scale_pages=ks, v_scale_pages=vs)
    k_d, v_d = gather_kv(kq, bt, 0, KH), gather_kv(vq, bt, 0, KH)
    ks_d, vs_d = gather_pages(ks, bt), gather_pages(vs, bt)
    want = decode_attention(q[:, None], k_d, v_d, lens,
                            k_scale=ks_d, v_scale=vs_d)[:, 0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_backend_dispatch_interpret_vs_xla():
    """The public op under ``kernel_interpret`` matches the ``xla`` backend
    (the engine scopes exactly this switch around its decode traces)."""
    B, H, KH, D, n_blocks, bs, pps = 3, 6, 3, 32, 12, 8, 3
    q, kp, vp, bt, lens = _case(21, B, H, KH, D, n_blocks, bs, pps, 20)
    with bp_matmul.use_matmul_backend("xla"):
        want = paged_attention(q, kp, vp, bt, lens)
    with bp_matmul.use_matmul_backend("kernel_interpret"):
        got = paged_attention(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_all_zero_length_row_is_finite():
    """A fresh slot (length 0, table all trash) must still produce finite
    output — only position 0 of the trash page is unmasked."""
    B, H, KH, D, n_blocks, bs, pps = 2, 2, 1, 8, 6, 4, 3
    q, kp, vp, _, _ = _case(31, B, H, KH, D, n_blocks, bs, pps, 10)
    bt = jnp.zeros((B, pps), jnp.int32)
    lens = jnp.zeros(B, jnp.int32)
    for backend in ("xla", "kernel_interpret"):
        with bp_matmul.use_matmul_backend(backend):
            out = paged_attention(q, kp, vp, bt, lens)
        assert bool(jnp.isfinite(out).all())


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_kernel_reads_its_layer_of_the_stacked_pool(layer):
    """The layer rides in as a scalar-prefetch operand: the kernel reads
    layer ``layer`` of the stacked pool where it lies.  Lengths sit on the
    block edges (last slot of a block, first of the next, position 0, the
    table's last position) and every table ends in trash-page entries."""
    B, H, KH, D, n_blocks, bs, pps, L = 5, 4, 2, 16, 24, 4, 5, 3
    q, kp, vp, _, _ = _case(41, B, H, KH, D, n_blocks, bs, pps, 1, L=L)
    lens = np.asarray([bs - 1, bs, 0, 2 * bs - 1, pps * bs - 1], np.int32)
    rng = np.random.default_rng(41)
    bt = np.zeros((B, pps), np.int32)
    for b in range(B):
        used = lens[b] // bs + 1
        bt[b, :used] = rng.choice(np.arange(1, n_blocks), size=used,
                                  replace=False)
    bt, lens = jnp.asarray(bt), jnp.asarray(lens)
    got = paged_attention_kernel(q, kp, vp, bt, lens, jnp.int32(layer),
                                 interpret=True)
    want = paged_attention_xla(q, kp, vp, bt, lens, layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # the same layer cut out and stacked alone: the oracle's layer index
    # reads the layer it names, and the other layers do not reach it
    alone = paged_attention_xla(q, kp[layer:layer + 1], vp[layer:layer + 1],
                                bt, lens)
    np.testing.assert_allclose(np.asarray(want), np.asarray(alone),
                               atol=1e-6, rtol=1e-6)
    other = paged_attention_xla(q, kp, vp, bt, lens, (layer + 1) % L)
    assert not np.allclose(np.asarray(other), np.asarray(want))


def test_gather_pages_reads_token_major_rows():
    """A page's row is one token of every KV head: ``gather_kv`` splits it
    back into (KH, D) in order, the slab cache layout."""
    L, N, bs, KH, D = 2, 4, 2, 3, 5
    pages = jnp.arange(L * N * bs * KH * D, dtype=jnp.float32).reshape(
        L, N, bs, KH * D)
    bt = jnp.asarray([[2, 1]], jnp.int32)
    dense = gather_kv(pages, bt, 1, KH)                 # (1, 4, KH, D)
    want = np.asarray(pages[1])[[2, 2, 1, 1], [0, 1, 0, 1]].reshape(
        4, KH, D)
    np.testing.assert_array_equal(np.asarray(dense[0]), want)
    np.testing.assert_array_equal(
        np.asarray(gather_pages(pages, bt, 1))[0],
        want.reshape(4, KH * D))
