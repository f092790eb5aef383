"""Value- and bit-level sparsity statistics (paper Fig. 1 and Section IV-B3).

The paper measures bit-level sparsity of 8-bit quantized tensors in
sign-magnitude representation (7 magnitude bits per element) and contrasts it
with 2's-complement, which exhibits lower sparsity for negative values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bitparticle import _popcount7, to_sign_magnitude


def value_sparsity(q):
    """Fraction of exactly-zero elements."""
    q = jnp.asarray(q)
    return jnp.mean((q == 0).astype(jnp.float32))


def bit_sparsity_sign_magnitude(q, nonzero_only: bool = False):
    """Mean fraction of zero bits among the 7 magnitude bits.

    ``nonzero_only`` restricts the average to nonzero elements (the paper's
    "bit-level sparsity of non-zero elements", Section IV-B3).
    """
    _, mag = to_sign_magnitude(q)
    zeros = 7 - _popcount7(mag)
    frac = zeros.astype(jnp.float32) / 7.0
    if nonzero_only:
        m = (mag != 0).astype(jnp.float32)
        return jnp.sum(frac * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(frac)


#: :func:`bit_sparsity_sign_magnitude` of a whole tensor as ONE compiled
#: reduction: XLA fuses the sign-magnitude split, the popcount and the mean,
#: so no int32 or float32 temporary the size of the tensor is built (the
#: eager form holds several at once, which a full-width weight stack cannot
#: afford beside a KV pool)
bit_sparsity_fused = jax.jit(bit_sparsity_sign_magnitude,
                             static_argnames="nonzero_only")


def popcount8(u):
    """Set-bit count of the low 8 bits, via a broadcast bit expansion."""
    u = jnp.asarray(u, jnp.int32)
    return jnp.sum((u[..., None] >> jnp.arange(8)) & 1, axis=-1)


def bit_sparsity_twos_complement(q):
    """Mean fraction of zero bits among all 8 bits of the 2's-complement form."""
    q = jnp.asarray(q, jnp.int32)
    u = jnp.where(q < 0, q + 256, q)  # 8-bit two's complement pattern
    return jnp.mean((8 - popcount8(u)).astype(jnp.float32) / 8.0)


# Per-tensor stat rows used by the serving probe: a fixed-width float32
# vector whose entries are pure sums, so rows from different tensors (or
# different devices) add together exactly before being turned into rates.
N_STATS = 3  # [sum of zero magnitude bits, n elements, n zero values]


def sm_bit_stats(q):
    """``(N_STATS,)`` float32 sum-form sparsity stats of one int8 tensor.

    ``stats_to_rates`` recovers ``bit_sparsity_sign_magnitude`` /
    ``value_sparsity`` exactly: the bit sparsity here is the element-weighted
    mean, identical to ``mean((7 - popcount7(mag)) / 7)``.
    """
    _, mag = to_sign_magnitude(q)
    zero_bits = (7 - _popcount7(mag)).astype(jnp.float32)
    return jnp.stack([jnp.sum(zero_bits),
                      jnp.float32(mag.size),
                      jnp.sum((mag == 0).astype(jnp.float32))])


def per_layer_stats(q):
    """``(L, N_STATS)`` stats of a layer-stacked int8 tensor (leading axis L)."""
    q = jnp.asarray(q)
    _, mag = to_sign_magnitude(q.reshape(q.shape[0], -1))
    zero_bits = (7 - _popcount7(mag)).astype(jnp.float32)
    n = jnp.full((q.shape[0],), mag.shape[1], jnp.float32)
    return jnp.stack([jnp.sum(zero_bits, axis=1), n,
                      jnp.sum((mag == 0).astype(jnp.float32), axis=1)],
                     axis=-1)


def stats_to_rates(stats):
    """(bit_sparsity, value_sparsity) from summed ``sm_bit_stats`` rows.

    Works on a single ``(N_STATS,)`` row or a stacked ``(..., N_STATS)``
    array; zero-element rows yield 0.0 rather than NaN.
    """
    stats = jnp.asarray(stats, jnp.float32)
    n = jnp.maximum(stats[..., 1], 1.0)
    return stats[..., 0] / (7.0 * n), stats[..., 2] / n


def sample_with_bit_sparsity(key, shape, bit_sparsity: float, value_sparsity_p: float = 0.0):
    """Generate sign-magnitude int operands matching the paper's generator.

    Each of the 7 magnitude bits is independently 0 with probability
    ``bit_sparsity``; sign is uniform; optionally a fraction
    ``value_sparsity_p`` of elements is forced to exact zero.
    (Section IV-B3: "assigns each bit a probability of bs to be 0".)
    """
    import jax

    kb, ks, kz = jax.random.split(key, 3)
    bits = jax.random.bernoulli(kb, 1.0 - bit_sparsity, shape + (7,))
    mag = jnp.sum(bits.astype(jnp.int32) << jnp.arange(7), axis=-1)
    sign = jax.random.bernoulli(ks, 0.5, shape)
    val = jnp.where(sign, -mag, mag)
    if value_sparsity_p > 0.0:
        zero = jax.random.bernoulli(kz, value_sparsity_p, shape)
        val = jnp.where(zero, 0, val)
    return val
