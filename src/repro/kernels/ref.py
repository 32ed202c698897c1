"""Pure-jnp oracles for every Pallas kernel (the correctness ground
truth for the interpret-mode sweeps in tests/test_kernels.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ------------------------------------------------- dither quantize+pack
def dither_encode_ref(x, s, w, bits: int):
    """m = floor(x/w + s + 1/2) clamped to the signed ``bits`` range."""
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    m = jnp.floor(x / w + s + 0.5)
    return jnp.clip(m, lo, hi).astype(jnp.int32)


def pack_ref(m, bits: int):
    """Pack groups of (32 // bits) signed ints into int32 words over the
    second-to-last axis: m (..., G, C) -> (..., C)."""
    g = 32 // bits
    assert m.shape[-2] == g
    mask = (1 << bits) - 1
    word = jnp.zeros(m.shape[:-2] + m.shape[-1:], jnp.int32)
    for j in range(g):
        word = word | ((m[..., j, :] & mask) << (bits * j))
    return word


def unpack_ref(word, bits: int):
    """Inverse of pack_ref with sign extension: (..., C) -> (..., G, C)."""
    g = 32 // bits
    outs = []
    for j in range(g):
        v = (word << (32 - bits * (j + 1))) >> (32 - bits)  # arithmetic
        outs.append(v)
    return jnp.stack(outs, axis=-2)


def dither_pack_ref(x, s, w, bits: int):
    """Fused oracle: x, s (..., G, C) -> packed int32 (..., C)."""
    return pack_ref(dither_encode_ref(x, s, w, bits), bits)


def unpack_decode_ref(word, s, w, bits: int):
    """Fused oracle: packed words + dither -> dequantized values."""
    m = unpack_ref(word, bits)
    return (m.astype(jnp.float32) - s) * w


# --------------------------------------- fused homomorphic encode/decode
def fused_encode_ref(x, s, step, bits: int, m_max: int):
    """Oracle for fused_agg._encode_kernel: clip -> dither-quantize ->
    bias -> unsigned-pack.  x, s (and array ``step``) are (..., G, C)
    with G = 32 // bits; returns packed int32 words (..., C)."""
    g = max(32 // bits, 1)
    m = jnp.clip(jnp.floor(x / step + s + 0.5), float(-m_max), float(m_max))
    u = m.astype(jnp.int32) + m_max
    word = jnp.zeros(u.shape[:-2] + u.shape[-1:], jnp.int32)
    for j in range(g):
        word = word | (u[..., j, :] << (bits * j))
    return word


def unpack_biased_ref(word, bits: int):
    """Unsigned-field unpack of (summed) biased words: (..., C) ->
    (..., G, C) int32 field sums."""
    g = max(32 // bits, 1)
    mask = (1 << bits) - 1
    return jnp.stack(
        [(word >> (bits * j)) & mask for j in range(g)], axis=-2
    )


def fused_decode_ref(word, bias_sum, s_sum, step, offset, bits: int):
    """Oracle for fused_agg._decode_kernel: unpack, subtract the packing
    bias (r * m_max) in int32 and the dither sum in f32, rescale
    [+ offset]."""
    m = (unpack_biased_ref(word, bits) - bias_sum).astype(jnp.float32)
    y = (m - s_sum) * step
    return y if offset is None else y + offset


# ------------------------------------------------- shifted layered encode
def layered_encode_ref(x, u, layer, sigma: float):
    """Fused shifted-layered-quantizer encode for a Gaussian target:
    step  = b+(W) + b+(peak - W),  m = floor(x/step + u)."""
    import math

    s = sigma
    peak = 1.0 / (s * math.sqrt(2.0 * math.pi))

    def b_plus(v):
        arg = -2.0 * jnp.log(jnp.clip(v * s * math.sqrt(2.0 * math.pi), 1e-37, 1.0))
        return s * jnp.sqrt(jnp.maximum(arg, 0.0))

    step = b_plus(layer) + b_plus(peak - layer)
    return jnp.floor(x / step + u).astype(jnp.int32)


# ------------------------------------------------- flash attention
def mha_ref(q, k, v, causal: bool = True):
    """q (B, T, H, D), k/v (B, S, H, D) -> (B, T, H, D), fp32 softmax."""
    B, T, H, D = q.shape
    S = k.shape[1]
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * (D**-0.5)
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v)
