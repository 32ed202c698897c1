"""Pallas kernel sweeps (interpret mode) against the ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributions import Gaussian
from repro.core.layered import LayeredQuantizer
from repro.kernels import ops, ref


@pytest.mark.parametrize("bits", [4, 8, 16])
@pytest.mark.parametrize("shape", [(128,), (1000, 37), (3, 5, 7, 11)])
def test_dither_pack_roundtrip(bits, shape):
    key = jax.random.PRNGKey(hash((bits, shape)) & 0xFFFF)
    x = jax.random.normal(key, shape) * 0.1
    s = jax.random.uniform(jax.random.fold_in(key, 1), shape, minval=-0.5, maxval=0.5)
    w = 0.05
    packed, n = ops.dither_pack_encode(x, s, w, bits=bits)
    assert packed.dtype == jnp.int32 and n == int(np.prod(shape))
    y = ops.dither_unpack_decode(packed, s, w, bits, shape)
    m_ref = ref.dither_encode_ref(x, s, w, bits)
    y_ref = (m_ref.astype(jnp.float32) - s) * w
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-6)


@pytest.mark.parametrize("bits", [8, 16])
def test_dither_pack_error_is_uniform(bits):
    """End-to-end: the kernel pipeline is still an exact AINQ quantizer."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (20000,)) * 0.3
    s = jax.random.uniform(jax.random.fold_in(key, 1), x.shape, minval=-0.5, maxval=0.5)
    w = 0.05
    packed, _ = ops.dither_pack_encode(x, s, w, bits=bits)
    y = ops.dither_unpack_decode(packed, s, w, bits, x.shape)
    err = np.asarray(y - x)
    assert abs(err.std() - w / np.sqrt(12)) < w * 0.02
    assert np.abs(err).max() <= w / 2 + 1e-6


@pytest.mark.parametrize("bits,m_max", [(4, 3), (8, 25), (16, 4000), (24, 80000)])
@pytest.mark.parametrize("percoord", [False, True])
def test_fused_agg_kernel_vs_oracle(bits, m_max, percoord):
    """fused_agg encode/decode (interpret) against the jnp oracles:
    identical packed words, matching affine decode, scalar and
    per-coordinate step."""
    from repro.kernels import fused_agg as fg

    shape = (1000, 37)
    key = jax.random.PRNGKey(bits * 2 + percoord)
    x = jax.random.uniform(key, shape, minval=-1.0, maxval=1.0)
    s = jax.random.uniform(jax.random.fold_in(key, 1), shape,
                           minval=-0.5, maxval=0.5)
    base = 1.0 / (m_max - 1)
    if percoord:
        step = base * jax.random.uniform(
            jax.random.fold_in(key, 2), shape, minval=0.5, maxval=1.5)
    else:
        step = base
    w_p = ops.fused_pack_encode(x, s, step, bits, m_max, impl="pallas")
    w_x = ops.fused_pack_encode(x, s, step, bits, m_max, impl="xla")
    assert w_p.dtype == jnp.int32
    assert bool(jnp.all(w_p == w_x))
    offset = None if percoord else 0.125
    # one message summed: r = 1, packing bias m_max
    y_p = ops.fused_unpack_decode(w_p, s, m_max, step, offset, bits, shape,
                                  impl="pallas")
    y_x = ops.fused_unpack_decode(w_x, s, m_max, step, offset, bits, shape,
                                  impl="xla")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x), atol=1e-6)
    m = jnp.clip(jnp.floor(x / step + s + 0.5), -m_max, m_max)
    y_ref = (m - s) * step + (0.0 if offset is None else offset)
    # the eager reference can land one step away at exact floor-boundary
    # ties (fused-multiply contraction); a bias bug would be >= m_max*step
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_ref),
                               atol=1.05 * base + 1e-5)
    assert fg.LANES == 128  # layout contract shared with ops._pad_rows


@pytest.mark.parametrize("sigma", [0.01, 0.5])
@pytest.mark.parametrize("shape", [(256,), (130, 77)])
def test_layered_kernel_matches_core(sigma, shape):
    q = LayeredQuantizer(Gaussian(sigma), shifted=True)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, shape) * 3 * sigma
    u, layer = q.randomness(jax.random.fold_in(key, 1), shape)
    m_k = ops.layered_encode(x, u, layer, sigma)
    m_c = q.encode(x, (u, layer))
    assert bool(jnp.all(m_k == m_c))
    y_k = ops.layered_decode(m_k, u, layer, sigma)
    y_c = q.decode(m_c, (u, layer))
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_c), atol=1e-5)


@pytest.mark.parametrize(
    "B,T,S,H,HK,D,causal",
    [
        (2, 128, 128, 4, 2, 64, True),
        (1, 256, 256, 2, 2, 32, True),
        (2, 64, 192, 4, 4, 16, False),
        (1, 96, 96, 2, 1, 128, True),  # non-multiple of block
    ],
)
def test_flash_attention_vs_ref(B, T, S, H, HK, D, causal):
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (B, T, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, HK, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, HK, D), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    kr = jnp.repeat(k, H // HK, 2)
    vr = jnp.repeat(v, H // HK, 2)
    o_ref = ref.mha_ref(q, kr, vr, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


def test_jax_chunked_attention_vs_ref():
    """The pure-JAX fallback (models.attention) against the oracle."""
    from repro.models.attention import flash_attention as jf

    key = jax.random.PRNGKey(13)
    B, T, H, HK, D = 2, 160, 4, 2, 32
    q = jax.random.normal(key, (B, T, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, HK, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, HK, D), jnp.float32)
    o = jf(q, k, v, causal=True, q_chunk=64, kv_chunk=32)
    o_ref = ref.mha_ref(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


def test_jax_attention_sliding_window():
    from repro.models.attention import flash_attention as jf

    key = jax.random.PRNGKey(17)
    B, T, H, D, W = 1, 128, 2, 16, 32
    q = jax.random.normal(key, (B, T, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, H, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, H, D))
    o = jf(q, k, v, causal=True, window=W, q_chunk=32, kv_chunk=32)
    # oracle with explicit banded mask
    s = jnp.einsum("bthd,bshd->bhts", q, k) * D**-0.5
    i = jnp.arange(T)
    mask = (i[None, :] <= i[:, None]) & (i[None, :] > i[:, None] - W)
    s = jnp.where(mask[None, None], s, -1e30)
    o_ref = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
