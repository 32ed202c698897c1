"""Compressed cross-client gradient aggregation on a mesh axis.

This is the SPMD face of the paper's AINQ mechanisms: inside a
``shard_map`` that is manual over the 'pod' (client) axis, every pod
clips and encodes its gradient tree into integer messages, the messages
are aggregated with an integer ``psum`` (the homomorphic /
secure-aggregation-shaped collective), and every pod decodes the *sum* —
so the aggregated error follows the mechanism's law exactly:

  aggregate_gaussian — N(0, sigma^2) exactly (paper Prop. 3)
  aggregate_laplace  — Laplace(0, sigma/sqrt(2)) exactly (same DECOMPOSE
                       machinery with the Laplace target tables)
  irwin_hall         — IH(n, 0, sigma^2) exactly (Sec. 4.2)
  layered_shifted    — per-client N(0, n sigma^2) decoded locally and
                       pmean'd -> N(0, sigma^2) exactly (Def. 5; not
                       homomorphic: the collective carries floats)
  layered_direct     — as above with the direct layering (Def. 4)
  none_              — clip + pmean (no quantization)

Shared randomness is derived from one replicated per-round key: the
global (A, B) draw uses it directly, client i's dither uses
``fold_in(key, i)`` with i = the pod's ``axis_index``, and the decode
recomputes every client's dither from the same seed — only integers
ever cross pods for the homomorphic mechanisms.

Two wire formats for the homomorphic mechanisms:

  * unfused (default): one signed ``msg_dtype`` word per coordinate,
    clip / dither / quantize as separate XLA ops — the always-available
    reference path.
  * fused (``CompressionConfig(fused=True)``): clip + dither-add +
    quantize + bias + bit-pack run in ONE kernel pass per direction
    (``repro.kernels.fused_agg``; the XLA-fused oracle on CPU), and the
    psum carries b-bit fields packed into int32 words — collective
    bytes shrink by ~b/32 (see ``repro.core.packing``).  Both paths
    clamp messages to the same ``PackGeometry``, so they produce
    bit-identical messages and the same exact error law.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import debug
from repro.core import coding, dither
from repro.core.aggregate import AggregateGaussianMechanism
from repro.core.distributions import Gaussian
from repro.core.irwin_hall import IrwinHallMechanism
from repro.core.layered import LayeredQuantizer
from repro.core.packing import PackGeometry, geometry_for_range
from repro.kernels import ops

PyTree = Any

MECHANISMS = (
    "none_",
    "aggregate_gaussian",
    "aggregate_laplace",
    "irwin_hall",
    "layered_shifted",
    "layered_direct",
)

HOMOMORPHIC = ("aggregate_gaussian", "aggregate_laplace", "irwin_hall")

_MSG_DTYPES = {"int32": jnp.int32, "int16": jnp.int16, "int8": jnp.int8}

# default packed field width per psum payload dtype: the widest field
# whose biased sums (a) fit the dtype's signed range in the unfused
# reference and (b) stay f32-exact (<= 2^24) in the fused decode
_DEFAULT_PACK_BITS = {"int32": 24, "int16": 15, "int8": 7}


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Cross-client compression for the training hot path.

    mechanism: one of MECHANISMS.
    sigma:     std of the *aggregated* error.
    clip:      per-coordinate clip applied to each client's gradient
               before encoding (also the DP sensitivity knob).
    msg_dtype: integer payload of the cross-pod psum ("int32"/"int16"/
               "int8") on the unfused path; narrower payloads shrink
               the collective but can wrap for tiny shared steps unless
               ``msg_bits`` pins the geometry.
    per_coord: one (A, B) shared draw per coordinate (paper-faithful,
               i.i.d. noise, required for DP and the KS tests) vs one
               per tensor (cheaper RNG, coordinates dependent).
    fused:     run the homomorphic mechanisms through the fused
               encode/decode kernels with true-bit-width packed psum
               payloads (homomorphic mechanisms only).
    msg_bits:  packed field width b for the aggregate mechanisms (their
               step scale A is clamped so messages fit); for irwin_hall
               an upper bound on the derived natural width.  None picks
               the ``msg_dtype`` default.  Setting it also clamps the
               unfused reference to the same geometry, keeping the two
               paths bit-identical.
    """

    mechanism: str = "aggregate_gaussian"
    sigma: float = 1e-4
    clip: float = 1.0
    msg_dtype: str = "int32"
    per_coord: bool = True
    fused: bool = False
    msg_bits: Optional[int] = None

    def __post_init__(self):
        if self.mechanism not in MECHANISMS:
            raise KeyError(
                f"unknown mechanism {self.mechanism!r}; have {MECHANISMS}"
            )
        if self.mechanism != "none_" and not self.sigma > 0.0:
            raise ValueError(f"sigma must be > 0, got {self.sigma}")
        if self.msg_dtype not in _MSG_DTYPES:
            raise KeyError(f"msg_dtype {self.msg_dtype!r} not in {_MSG_DTYPES}")
        if self.fused and self.mechanism not in HOMOMORPHIC:
            raise ValueError(
                f"fused packing needs an integer-homomorphic mechanism "
                f"({HOMOMORPHIC}), got {self.mechanism!r}"
            )
        if self.msg_bits is not None and not 2 <= self.msg_bits <= 24:
            raise ValueError(
                f"msg_bits must be in [2, 24], got {self.msg_bits}"
            )


def _client_index(axis: Optional[str]):
    return jax.lax.axis_index(axis) if axis is not None else 0


def _dither_sum(ks, n: int, shape) -> jnp.ndarray:
    """sum_j S_j recomputed from the shared seed (every pod holds the
    round key, so no float collective is needed for the dither sum).
    One batched key derivation + one vmapped draw — the traced graph no
    longer grows with the cohort size."""
    keys = jax.vmap(lambda j: jax.random.fold_in(ks, j))(jnp.arange(n))
    return jax.vmap(lambda k: dither.dither_noise(k, shape))(keys).sum(0)


def _psum_msg(m, comp: CompressionConfig, axis: Optional[str]):
    if comp.fused:
        # packed words are already the narrow payload; sum as int32
        return jax.lax.psum(m, axis) if axis is not None else m
    m = m.astype(_MSG_DTYPES[comp.msg_dtype])
    if axis is not None:
        # repro-lint: disable=int-width-discipline -- legacy unfused
        # narrow-dtype path: geometry is clamped upstream when msg_bits
        # is set; without it the documented wrap risk is the caller's
        # (CompressionConfig docstring)
        m = jax.lax.psum(m, axis)
    return m.astype(jnp.int32)


# --------------------------------------------------- homomorphic leaf codec
def _make_mech(comp: CompressionConfig, n: int):
    if comp.mechanism in ("aggregate_gaussian", "aggregate_laplace"):
        return AggregateGaussianMechanism(
            n, comp.sigma, comp.per_coord,
            family=comp.mechanism.removeprefix("aggregate_"),
        )
    return IrwinHallMechanism(n, comp.sigma)


def leaf_geometry(comp: CompressionConfig, n: int) -> Optional[PackGeometry]:
    """Packed-field geometry of one homomorphic leaf, or None when the
    config runs the legacy unclamped int32 path (not fused, no msg_bits).
    """
    if comp.mechanism not in HOMOMORPHIC:
        return None
    if not comp.fused and comp.msg_bits is None:
        return None
    n = max(int(n), 1)
    bits = (comp.msg_bits if comp.msg_bits is not None
            else _DEFAULT_PACK_BITS[comp.msg_dtype])
    mech = _make_mech(comp, n)
    if isinstance(mech, IrwinHallMechanism):
        # natural range, capped at the configured width (the cap clamps
        # rarely-hit extreme messages; the unfused reference clamps too)
        m_nat = math.ceil(comp.clip / mech.w) + 1
        m_cap = ((1 << bits) - 1) // (2 * n)
        return geometry_for_range(min(m_nat, max(m_cap, 2)), n)
    return mech.pack_geometry(bits)


def _leaf_params(comp: CompressionConfig, n: int, kt, shape) -> Tuple[
        Any, Optional[jnp.ndarray], Optional[PackGeometry]]:
    """(step, offset, geometry) of a homomorphic leaf: step is the
    dither step (scalar w, or the shared per-coordinate A*w array),
    offset the shared additive term (B*sigma, or None)."""
    mech = _make_mech(comp, n)
    geom = leaf_geometry(comp, n)
    if isinstance(mech, AggregateGaussianMechanism):
        a_min = (mech.a_min_for_geometry(comp.clip, geom)
                 if geom is not None
                 else mech.a_min_for_range(2.0 * comp.clip))
        t = mech.global_randomness(kt, shape, a_min=a_min)
        return t.A * mech.w, t.B * comp.sigma, geom
    return mech.w, None, geom


def encode_leaf(x32, comp: CompressionConfig, step, s_i,
                geom: Optional[PackGeometry]):
    """One client's integer message for a clipped f32 leaf: biased
    packed int32 words when fused, else the signed per-coordinate
    message (clamped to the shared geometry when one is active)."""
    if debug.active():
        debug.check(jnp.all(jnp.isfinite(x32)),
                    "encode: non-finite input leaf")
        if geom is not None and comp.mechanism != "irwin_hall":
            # aggregate mechanisms size a_min so the natural (pre-clamp)
            # message fits the b-bit field; a violation means the A
            # clamp upstream is wrong and the clamped message silently
            # biases the decoded mean.  (irwin_hall is exempt: its
            # geometry cap clamps extreme messages by design.)
            m_raw = dither.dither_encode(x32, step, s_i)
            debug.check(
                jnp.all(jnp.abs(m_raw) <= geom.m_max),
                "encode: message overflows the b-bit field "
                "(|m| > m_max={m_max})", m_max=jnp.int32(geom.m_max))
    if comp.fused:
        return ops.fused_pack_encode(x32, s_i, step, geom.bits, geom.m_max)
    m = dither.dither_encode(x32, step, s_i)
    if geom is not None:
        m = jnp.clip(m, -geom.m_max, geom.m_max)
    return m


def decode_leaf_sum(m_sum, comp: CompressionConfig, n, r_msgs,
                    step, offset, s_sum, geom: Optional[PackGeometry],
                    shape):
    """Decode the SUM of ``r_msgs`` messages (psum output, or the
    server's masked sum) into the across-clients mean + exact noise.
    ``n`` is the decode divisor (the cohort size, or the runtime's
    traced realized count for straggler renormalization); ``r_msgs``
    the number of messages actually summed (their packing biases must
    be removed)."""
    step_dec = step / n  # python float stays scalar; arrays stay arrays
    if comp.fused:
        if debug.active():
            # each packed field carries sum_i (m_i + bias) over the
            # r_msgs summed messages; anything above r_msgs * 2 * m_max
            # means a tampered/overflowed lane that the bias-stripping
            # decode below would silently turn into a wrong mean
            fields = jnp.stack([
                (m_sum.astype(jnp.uint32) >> jnp.uint32(geom.bits * j))
                & jnp.uint32((1 << geom.bits) - 1)
                for j in range(geom.group)
            ])
            debug.check(
                jnp.all(fields <= jnp.uint32(r_msgs * 2 * geom.m_max)),
                "decode: packed field sum exceeds r * 2 * m_max "
                "(overflowed or tampered lane)")
        bias_sum = jnp.asarray(r_msgs).astype(jnp.int32) * geom.bias
        y = ops.fused_unpack_decode(
            m_sum, s_sum, bias_sum, step_dec, offset, geom.bits, shape
        )
        if debug.active():
            debug.check(jnp.all(jnp.isfinite(y)),
                        "decode: non-finite output (fused path)")
        return y
    if debug.active() and geom is not None:
        debug.check(
            jnp.all(jnp.abs(m_sum) <= r_msgs * geom.m_max),
            "decode: summed message exceeds r * m_max for the "
            "declared geometry")
    y = (m_sum.astype(jnp.float32) - s_sum) * step_dec
    if debug.active():
        debug.check(jnp.all(jnp.isfinite(y)),
                    "decode: non-finite output")
    return y if offset is None else y + offset


def _compress_leaf(x, comp: CompressionConfig, key, axis: Optional[str],
                   n: int):
    dtype = x.dtype
    with jax.named_scope("encode"):
        x32 = jnp.clip(x.astype(jnp.float32), -comp.clip, comp.clip)
    shape = x32.shape

    if comp.mechanism == "none_":
        y = jax.lax.pmean(x32, axis) if axis is not None else x32
        return y.astype(dtype)

    kt, ks = jax.random.split(key)
    idx = _client_index(axis)

    if comp.mechanism in HOMOMORPHIC:
        # fl.codec/<part> scopes name each part's device time in a trace
        with jax.named_scope("draw"):
            step, offset, geom = _leaf_params(comp, n, kt, shape)
        with jax.named_scope("dither"):
            s_i = dither.dither_noise(jax.random.fold_in(ks, idx), shape)
        with jax.named_scope("encode"):
            m = encode_leaf(x32, comp, step, s_i, geom)
        with jax.named_scope("psum"):
            m_sum = _psum_msg(m, comp, axis)
        if axis is not None:
            with jax.named_scope("dither"):
                s_sum, r_msgs = _dither_sum(ks, n, shape), n
        else:
            s_sum, r_msgs = s_i, 1
        with jax.named_scope("decode"):
            y = decode_leaf_sum(m_sum, comp, n, r_msgs, step, offset, s_sum,
                                geom, shape)
            return y.astype(dtype)

    if comp.mechanism in ("layered_shifted", "layered_direct"):
        # point-to-point AINQ per client (per-client noise N(0, n s^2)
        # averages to N(0, s^2)); decode locally, average the floats.
        q = LayeredQuantizer(
            Gaussian(comp.sigma * math.sqrt(n)),
            shifted=comp.mechanism == "layered_shifted",
        )
        rand = q.randomness(jax.random.fold_in(ks, idx), shape)
        y = q.decode(q.encode(x32, rand), rand)
        if axis is not None:
            y = jax.lax.pmean(y, axis)
        return y.astype(dtype)

    raise KeyError(comp.mechanism)


def compress_tree(grads: PyTree, comp: CompressionConfig, key,
                  axis: Optional[str] = None, n_clients: int = 1) -> PyTree:
    """Compress-aggregate a gradient tree across ``axis``.

    Inside a shard_map manual over ``axis`` each caller holds its own
    client's gradients; the return value is the across-clients mean plus
    the mechanism's exact noise, identical on every client.  With
    ``axis=None`` (n_clients=1) this is the point-to-point mechanism:
    quantize + exact noise, no collective.
    """
    n = max(int(n_clients), 1)
    leaves, treedef = jax.tree.flatten(grads)
    with jax.named_scope("fl.codec"):
        out = [
            _compress_leaf(g, comp, jax.random.fold_in(key, i), axis, n)
            for i, g in enumerate(leaves)
        ]
    return jax.tree.unflatten(treedef, out)


# --------------------------------------------------------- bit accounting
def message_bits(comp: CompressionConfig, n_clients: int, *,
                 num_samples: int = 8192) -> float:
    """Per-coordinate message size (bits) one client sends per round,
    for inputs clipped to [-clip, clip].

    Fixed-length mechanisms report their exact code size; the
    variable-length ones (aggregate_gaussian, layered_direct) report the
    expected Elias-gamma length (Sec. 5.2) over a deterministic
    Monte-Carlo draw of the shared randomness and uniform inputs.
    """
    n = max(int(n_clients), 1)
    t = 2.0 * comp.clip
    if comp.mechanism == "none_":
        return 32.0
    if comp.mechanism == "irwin_hall":
        return float(IrwinHallMechanism(n, comp.sigma).bits_fixed(t))
    if comp.mechanism == "layered_shifted":
        q = LayeredQuantizer(Gaussian(comp.sigma * math.sqrt(n)), shifted=True)
        return float(q.fixed_bits(t))

    key = jax.random.PRNGKey(0)
    kx, kr = jax.random.split(key)
    x = jax.random.uniform(
        kx, (num_samples,), minval=-comp.clip, maxval=comp.clip
    )
    if comp.mechanism in ("aggregate_gaussian", "aggregate_laplace"):
        mech = AggregateGaussianMechanism(
            n, comp.sigma, comp.per_coord,
            family=comp.mechanism.removeprefix("aggregate_"),
        )
        tshared = mech.global_randomness(jax.random.fold_in(kr, 0), x.shape)
        s = mech.client_randomness(jax.random.fold_in(kr, 1), x.shape)
        m = mech.encode(x, s, tshared)
    elif comp.mechanism == "layered_direct":
        q = LayeredQuantizer(Gaussian(comp.sigma * math.sqrt(n)), shifted=False)
        rand = q.randomness(kr, x.shape)
        m = q.encode(x, rand)
    else:
        raise KeyError(comp.mechanism)
    return float(jnp.mean(coding.elias_gamma_bits(m)))


def wire_bits_per_coord(comp: CompressionConfig, n_clients: int,
                        size: Optional[int] = None) -> float:
    """Bits per coordinate a client's payload actually occupies on the
    collective: ``32 / group`` for the fused packed format (exact,
    including word padding, when ``size`` is given), else the unfused
    ``msg_dtype`` word width."""
    geom = leaf_geometry(comp, max(int(n_clients), 1))
    if comp.fused and geom is not None:
        if size:
            return 32.0 * geom.n_words(size) / size
        return 32.0 / geom.group
    return float(jnp.dtype(_MSG_DTYPES[comp.msg_dtype]).itemsize * 8)
