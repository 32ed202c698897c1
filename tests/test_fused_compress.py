"""Fused packed-collective codec (ISSUE 7): bitwise equivalence with
the unfused reference, exact error laws after fusion (KS), shard_map
end-to-end, and the packed runtime wire format."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from helpers import ks_statistic, ks_threshold, norm_cdf
from repro.core import dither
from repro.core.irwin_hall import NormalizedIrwinHall
from repro.core.packing import geometry_for_bits, geometry_for_range
from repro.dist import compress as dc
from repro.dist import meshctx
from repro.kernels import ops, ref
from repro.runtime import protocol

# bits=4 fields hold at most n=2 summed messages with m_max >= 2
N_FOR_BITS = {4: 2, 8: 4, 16: 4, 24: 4}
SIGMA = 0.02


def laplace_cdf(x, b):
    x = np.asarray(x)
    return np.where(x < 0, 0.5 * np.exp(x / b), 1 - 0.5 * np.exp(-x / b))


def ih_cdf_fn(n, sigma):
    ih = NormalizedIrwinHall(n)
    xs, fs = np.asarray(ih._xs64), np.asarray(ih._fs64)
    half = np.concatenate(
        [[0.0], np.cumsum((fs[1:] + fs[:-1]) / 2 * np.diff(xs))]
    )
    grid = np.concatenate([-xs[::-1], xs[1:]])
    cdfv = np.concatenate([0.5 - half[::-1], 0.5 + half[1:]])
    scale = sigma * math.sqrt(12 * n)
    return lambda z: np.interp(np.asarray(z) / scale, grid, cdfv)


def _cell(mechanism, bits, shape, key):
    """One fused/unfused codec cell with shared randomness drawn."""
    n = N_FOR_BITS[bits]
    comp_f = dc.CompressionConfig(mechanism=mechanism, sigma=SIGMA,
                                  clip=1.0, fused=True, msg_bits=bits)
    comp_u = dc.CompressionConfig(mechanism=mechanism, sigma=SIGMA,
                                  clip=1.0, fused=False, msg_bits=bits)
    kt, ks, kx = jax.random.split(key, 3)
    xs = jax.random.uniform(kx, (n,) + shape, minval=-1.0, maxval=1.0)
    step, offset, geom = dc._leaf_params(comp_f, n, kt, shape)
    keys = jax.vmap(lambda j: jax.random.fold_in(ks, j))(jnp.arange(n))
    ss = jax.vmap(lambda k: dither.dither_noise(k, shape))(keys)
    return comp_f, comp_u, n, xs, ss, step, offset, geom


# ------------------------------------------------- bitwise equivalence
@pytest.mark.parametrize("mechanism", dc.HOMOMORPHIC)
@pytest.mark.parametrize("bits", [4, 8, 16])
@pytest.mark.parametrize("shape", [(4096,), (1000, 37)])
def test_fused_messages_bitwise_equal_unfused(mechanism, bits, shape):
    """Unpacking the fused words recovers the unfused reference message
    exactly — same keys, same geometry, bit for bit."""
    key = jax.random.PRNGKey(hash((mechanism, bits, shape)) & 0xFFFF)
    comp_f, comp_u, n, xs, ss, step, offset, geom = _cell(
        mechanism, bits, shape, key)
    for i in range(n):
        words = dc.encode_leaf(xs[i], comp_f, step, ss[i], geom)
        m_u = dc.encode_leaf(xs[i], comp_u, step, ss[i], geom)
        # unpack layout mirrors ops._pad_rows: (R, G, 128) row-major is
        # the flat coordinate order
        fields = ref.unpack_biased_ref(words, geom.bits) - geom.bias
        m_f = fields.reshape(-1)[: m_u.size]
        assert bool(jnp.all(m_f == m_u.reshape(-1).astype(jnp.int32)))


@pytest.mark.parametrize("mechanism", dc.HOMOMORPHIC)
@pytest.mark.parametrize("bits", [4, 8, 16])
def test_fused_pallas_matches_xla_words(mechanism, bits):
    """The Pallas kernel (interpret mode) and the XLA-fused oracle
    produce identical packed words and matching decodes."""
    shape = (1000, 37)
    key = jax.random.PRNGKey(bits)
    comp_f, _, n, xs, ss, step, offset, geom = _cell(
        mechanism, bits, shape, key)
    w_p = ops.fused_pack_encode(xs[0], ss[0], step, geom.bits, geom.m_max,
                                impl="pallas")
    w_x = ops.fused_pack_encode(xs[0], ss[0], step, geom.bits, geom.m_max,
                                impl="xla")
    assert bool(jnp.all(w_p == w_x))
    y_p = ops.fused_unpack_decode(w_p, ss[0], geom.bias, step, offset,
                                  geom.bits, shape, impl="pallas")
    y_x = ops.fused_unpack_decode(w_x, ss[0], geom.bias, step, offset,
                                  geom.bits, shape, impl="xla")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x), atol=1e-6)


# ------------------------------------------------- aggregated decode
@pytest.mark.parametrize("mechanism", dc.HOMOMORPHIC)
@pytest.mark.parametrize("bits", [4, 8, 16, 24])
def test_fused_sum_decode_matches_unfused(mechanism, bits):
    """Summed packed words decode to the unfused sum decode (float ulp)."""
    shape = (8192,)
    key = jax.random.PRNGKey(100 + bits)
    comp_f, comp_u, n, xs, ss, step, offset, geom = _cell(
        mechanism, bits, shape, key)
    word_sum = sum(dc.encode_leaf(xs[i], comp_f, step, ss[i], geom)
                   for i in range(n))
    m_sum = sum(dc.encode_leaf(xs[i], comp_u, step, ss[i], geom)
                .astype(jnp.int32) for i in range(n))
    s_sum = ss.sum(0)
    y_f = dc.decode_leaf_sum(word_sum, comp_f, n, n, step, offset, s_sum,
                             geom, shape)
    y_u = dc.decode_leaf_sum(m_sum, comp_u, n, n, step, offset, s_sum,
                             geom, shape)
    # a bias-count or field-extraction bug would shift by >= m_max*step/n
    # = O(clip/n); 1e-3 only admits float reassociation noise
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_u), atol=1e-3)


# ------------------------------------------------- exact law after fusion
@pytest.mark.parametrize("mechanism,bits,sigma", [
    ("aggregate_gaussian", 16, 0.1),
    # the default int32 width: biased field sums near 2^24, where a
    # dither folded into the f32 bias would round to an integer
    ("aggregate_gaussian", 24, 0.1),
    ("aggregate_laplace", 16, 0.1),
    ("irwin_hall", 8, 5e-3),
])
def test_fused_error_law_ks(mechanism, bits, sigma):
    """The aggregated error of the FUSED path still follows the
    mechanism's exact law (sigmas chosen so the packed geometry's clamp
    mass is negligible at these widths)."""
    n, size = N_FOR_BITS[bits], 1 << 15
    comp = dc.CompressionConfig(mechanism=mechanism, sigma=sigma,
                                clip=1.0, fused=True, msg_bits=bits)
    key = jax.random.PRNGKey(7)
    kt, ks, kx = jax.random.split(key, 3)
    xs = jax.random.uniform(kx, (n, size), minval=-1.0, maxval=1.0)
    step, offset, geom = dc._leaf_params(comp, n, kt, (size,))
    keys = jax.vmap(lambda j: jax.random.fold_in(ks, j))(jnp.arange(n))
    ss = jax.vmap(lambda k: dither.dither_noise(k, (size,)))(keys)
    word_sum = sum(dc.encode_leaf(xs[i], comp, step, ss[i], geom)
                   for i in range(n))
    y = dc.decode_leaf_sum(word_sum, comp, n, n, step, offset, ss.sum(0),
                           geom, (size,))
    err = np.asarray(y - xs.mean(0))
    if mechanism == "aggregate_gaussian":
        cdf = lambda z: norm_cdf(z, sigma)
    elif mechanism == "aggregate_laplace":
        cdf = lambda z: laplace_cdf(z, sigma / math.sqrt(2.0))
    else:
        cdf = ih_cdf_fn(n, sigma)
    assert ks_statistic(err, cdf) < ks_threshold(size), mechanism


def test_fused_vs_unfused_two_sample_ks():
    """Different keys, same config: the fused and unfused error samples
    are draws from one distribution (two-sample KS)."""
    mechanism, bits, sigma, n, size = "irwin_hall", 8, 5e-3, 4, 1 << 14

    def errors(fused, seed):
        comp = dc.CompressionConfig(mechanism=mechanism, sigma=sigma,
                                    clip=1.0, fused=fused, msg_bits=bits)
        key = jax.random.PRNGKey(seed)
        kt, ks, kx = jax.random.split(key, 3)
        xs = jax.random.uniform(kx, (n, size), minval=-1.0, maxval=1.0)
        step, offset, geom = dc._leaf_params(comp, n, kt, (size,))
        keys = jax.vmap(lambda j: jax.random.fold_in(ks, j))(jnp.arange(n))
        ss = jax.vmap(lambda k: dither.dither_noise(k, (size,)))(keys)
        msum = sum(dc.encode_leaf(xs[i], comp, step, ss[i], geom)
                   .astype(jnp.int32) for i in range(n))
        y = dc.decode_leaf_sum(msum, comp, n, n, step, offset, ss.sum(0),
                               geom, (size,))
        return np.sort(np.asarray(y - xs.mean(0), np.float64))

    a, b = errors(True, 1), errors(False, 2)
    grid = np.concatenate([a, b])
    d = np.max(np.abs(
        np.searchsorted(a, grid, "right") / a.size
        - np.searchsorted(b, grid, "right") / b.size
    ))
    assert d < 1.95 * math.sqrt((a.size + b.size) / (a.size * b.size))


# ------------------------------------------------- shard_map end-to-end
def test_compress_tree_fused_psum_matches_unfused():
    """Across a real 8-pod mesh the fused packed psum reproduces the
    unfused collective's output and noise scale."""
    n, d, sigma = 8, 4096, 1e-3
    mesh = meshctx.make_mesh((8, 1, 1))
    xs = jax.random.uniform(jax.random.PRNGKey(0), (n, d),
                            minval=-0.5, maxval=0.5)
    for mechanism in dc.HOMOMORPHIC:
        kw = dict(mechanism=mechanism, sigma=sigma, clip=1.0, msg_bits=16)
        comp_f = dc.CompressionConfig(fused=True, **kw)
        comp_u = dc.CompressionConfig(fused=False, **kw)

        def agg(comp):
            def f(g):
                return dc.compress_tree(
                    {"g": g[0]}, comp, jax.random.PRNGKey(7),
                    axis="pod", n_clients=n,
                )["g"]
            return jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                 out_specs=P(), check_vma=False)

        y_f = agg(comp_f)(xs)
        y_u = agg(comp_u)(xs)
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_u),
                                   atol=1e-4, err_msg=mechanism)
        err = np.asarray(y_f - xs.mean(0))
        assert abs(err.std() - sigma) < 0.1 * sigma, (mechanism, err.std())


# ------------------------------------------------- packed runtime wire
def test_protocol_packed_roundtrip_and_straggler():
    """The packed uplink decodes the realized cohort subset with the
    announced-n step and realized-r renormalization."""
    d, n, sigma = 4096, 6, 1e-3
    key = protocol.round_key(3, 11)
    pp = protocol.RoundProtocol(mechanism="aggregate_gaussian",
                                sigma=sigma, packed=True)
    rng = np.random.default_rng(0)
    xs = rng.uniform(-1, 1, (n, d)).astype(np.float32)
    msgs = np.stack([pp.client_message(key, n, p, xs[p]) for p in range(n)])
    assert msgs.shape == (n, pp.payload_size(n, d))
    assert msgs.dtype == np.int32

    y, bits = pp.decode(key, n, msgs, np.ones(n, bool), d=d)
    err = np.asarray(y) - xs.mean(0)
    assert abs(err.std() - sigma) < 0.1 * sigma
    assert bits == pytest.approx(32.0 * msgs.shape[-1] / d)

    # straggler renormalization: decode the realized subset's mean
    mask = np.ones(n, bool)
    mask[[0, 3]] = False
    m2 = np.where(mask[:, None], msgs, 0)
    y2, _ = pp.decode(key, n, m2, mask, d=d)
    err2 = np.asarray(y2) - xs[mask].mean(0)
    # announced-n step with realized-r divisor keeps the error at the
    # mechanism's scale (not exactly sigma: the A-draw targets n)
    assert abs(err2.mean()) < 5 * sigma
    assert err2.std() < 3 * sigma


def test_protocol_packed_error_law_ks():
    d, n, sigma = 1 << 15, 6, 1e-3
    key = protocol.round_key(0, 7)
    pp = protocol.RoundProtocol(mechanism="aggregate_gaussian",
                                sigma=sigma, packed=True)
    rng = np.random.default_rng(1)
    xs = rng.uniform(-1, 1, (n, d)).astype(np.float32)
    msgs = np.stack([pp.client_message(key, n, p, xs[p]) for p in range(n)])
    y, _ = pp.decode(key, n, msgs, np.ones(n, bool), d=d)
    err = np.asarray(y) - xs.mean(0)
    assert ks_statistic(err, lambda t: norm_cdf(t, sigma)) < ks_threshold(d)


def test_protocol_packed_rejects_non_homomorphic():
    with pytest.raises(ValueError):
        protocol.RoundProtocol(mechanism="individual_shifted", packed=True)
    with pytest.raises(ValueError):
        pp = protocol.RoundProtocol(packed=True)
        pp.decode(jax.random.PRNGKey(0), 2, np.zeros((2, 128), np.int32),
                  np.ones(2, bool))  # missing d


# ------------------------------------------------- geometry validation
def test_pack_geometry_bounds():
    g = geometry_for_bits(8, 4)
    assert (g.bits, g.m_max, g.group) == (8, 31, 4)
    assert g.n_words(1000) == 250  # ceil(size / group), unpadded
    with pytest.raises(ValueError):
        geometry_for_bits(4, 4)  # per-client range would collapse
    g2 = geometry_for_range(30, 4)
    assert g2.bits == 8 and g2.m_max == 30
    with pytest.raises(ValueError):
        geometry_for_range(1 << 30, 8)  # needs > 32 bits


def test_config_validation():
    with pytest.raises(ValueError):
        dc.CompressionConfig(mechanism="layered_shifted", fused=True)
    with pytest.raises(ValueError):
        dc.CompressionConfig(msg_bits=1)
    with pytest.raises(ValueError):
        dc.CompressionConfig(msg_bits=31)
    with pytest.raises(ValueError):
        ops.fused_pack_encode(jnp.zeros(128), jnp.zeros(128), 0.1, 31, 10)
