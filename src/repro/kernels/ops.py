"""jit'd public wrappers around the Pallas kernels: shape padding /
layout handling so callers pass natural shapes.

``interpret=True`` (default on CPU) runs the kernel bodies in Python —
the validation mode for this container; on a real TPU pass
``interpret=False``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import dither_pack as dp
from repro.kernels import flash_attention as fa
from repro.kernels import fused_agg as fg
from repro.kernels import layered_encode as le
from repro.kernels import ref

LANES = 128


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_rows(x, g, value: float = 0.0):
    """Flatten to (R, g, 128) rows, padding with ``value`` (steps pad
    with 1.0 so padded lanes never divide by zero)."""
    row = g * LANES
    R = -(-x.size // row)
    pad = R * row - x.size
    flat = jnp.pad(x.reshape(-1), (0, pad), constant_values=value)
    return flat.reshape(R, g, LANES)


@functools.partial(jax.jit, static_argnames=("w", "bits", "interpret"))
def dither_pack_encode(x, s, w, bits: int = 8, interpret: bool | None = None):
    """Quantize+pack a tensor of any shape -> int32 words (R, 128).

    Returns (packed, orig_size). ``s`` must match x's shape
    (U(-1/2,1/2) shared randomness)."""
    interpret = _on_cpu() if interpret is None else interpret
    g = 32 // bits
    xr = _pad_rows(x, g)
    sr = _pad_rows(s, g)
    return dp.dither_pack(xr, sr, float(w), bits, interpret=interpret), x.size


@functools.partial(jax.jit, static_argnames=("w", "bits", "shape", "interpret"))
def dither_unpack_decode(word, s, w, bits: int, shape, interpret: bool | None = None):
    """Unpack+decode back to ``shape``."""
    interpret = _on_cpu() if interpret is None else interpret
    g = 32 // bits
    sr = _pad_rows(s, g)
    y = dp.unpack_decode(word, sr, float(w), bits, interpret=interpret)
    return y.reshape(-1)[: math.prod(shape)].reshape(shape)


# ------------------------------------------- fused homomorphic agg codec
def _impl_default(impl: str | None) -> str:
    """'pallas' on accelerators; the XLA-fused oracle on CPU, where the
    Pallas interpreter would run the kernel body tile-by-tile in Python.
    Pass impl='pallas' (+ interpret) explicitly to exercise the kernel."""
    if impl is None:
        return "xla" if _on_cpu() else "pallas"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be 'pallas' or 'xla', got {impl!r}")
    return impl


@functools.partial(
    jax.jit, static_argnames=("step", "bits", "m_max", "impl", "interpret")
)
def _fused_encode_scalar(x, s, step, bits, m_max, impl, interpret):
    g = max(32 // bits, 1)
    xr, sr = _pad_rows(x, g), _pad_rows(s, g)
    if impl == "xla":
        return ref.fused_encode_ref(xr, sr, step, bits, m_max)
    return fg.fused_encode(xr, sr, step, bits, m_max, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("bits", "m_max", "impl", "interpret")
)
def _fused_encode_percoord(x, s, step, bits, m_max, impl, interpret):
    g = max(32 // bits, 1)
    xr, sr = _pad_rows(x, g), _pad_rows(s, g)
    tr = _pad_rows(jnp.broadcast_to(step, x.shape), g, value=1.0)
    if impl == "xla":
        return ref.fused_encode_ref(xr, sr, tr, bits, m_max)
    return fg.fused_encode(xr, sr, tr, bits, m_max, interpret=interpret)


def fused_pack_encode(x, s, step, bits: int, m_max: int,
                      impl: str | None = None,
                      interpret: bool | None = None):
    """Fused clip-free homomorphic encode: dither-quantize ``x`` at
    ``step`` (python scalar, or array broadcastable to x.shape for the
    per-coordinate aggregate mechanisms), clamp to [-m_max, m_max],
    bias, and pack to ``bits``-wide unsigned fields -> int32 words
    (R, 128).  Packed words of different clients ADD homomorphically
    (core.packing); the caller clips x beforehand."""
    interpret = _on_cpu() if interpret is None else interpret
    impl = _impl_default(impl)
    # 24-bit cap: biased field sums stay <= 2^24, exactly representable
    # in the f32 decode (wider fields would silently lose low bits)
    if not 2 <= bits <= 24:
        raise ValueError(f"packed field width must be in [2, 24], got {bits}")
    if isinstance(step, (int, float)):
        return _fused_encode_scalar(x, s, float(step), bits, m_max, impl,
                                    interpret)
    return _fused_encode_percoord(x, s, step, bits, m_max, impl, interpret)


@functools.partial(
    jax.jit, static_argnames=("step", "bits", "shape", "impl", "interpret")
)
def _fused_decode_scalar(word, s_sum, bias_sum, step, offset, bits, shape,
                         impl, interpret):
    g = max(32 // bits, 1)
    ss = _pad_rows(s_sum, g)
    bs = jnp.asarray(bias_sum, jnp.int32).reshape(1, 1)
    off = None if offset is None else _pad_rows(
        jnp.broadcast_to(offset, s_sum.shape), g)
    if impl == "xla":
        y = ref.fused_decode_ref(word, bs, ss, step, off, bits)
    else:
        y = fg.fused_decode(word, bs, ss, step, off, bits,
                            interpret=interpret)
    return y.reshape(-1)[: math.prod(shape)].reshape(shape)


@functools.partial(
    jax.jit, static_argnames=("bits", "shape", "impl", "interpret")
)
def _fused_decode_percoord(word, s_sum, bias_sum, step, offset, bits, shape,
                           impl, interpret):
    g = max(32 // bits, 1)
    ss = _pad_rows(s_sum, g)
    bs = jnp.asarray(bias_sum, jnp.int32).reshape(1, 1)
    tr = _pad_rows(jnp.broadcast_to(step, s_sum.shape), g, value=1.0)
    off = None if offset is None else _pad_rows(
        jnp.broadcast_to(offset, s_sum.shape), g)
    if impl == "xla":
        y = ref.fused_decode_ref(word, bs, ss, tr, off, bits)
    else:
        y = fg.fused_decode(word, bs, ss, tr, off, bits, interpret=interpret)
    return y.reshape(-1)[: math.prod(shape)].reshape(shape)


def fused_unpack_decode(word, s_sum, bias_sum, step_dec, offset, bits: int,
                        shape, impl: str | None = None,
                        interpret: bool | None = None):
    """Fused homomorphic decode of SUMMED packed words back to ``shape``:
    unpack unsigned fields, subtract the packing bias ``bias_sum``
    (= r * m_max for r summed messages; int, may be traced) and the
    dither sum ``s_sum``, rescale by ``step_dec`` (mechanism step / n;
    scalar or array) and add ``offset`` (B * sigma, or None)."""
    interpret = _on_cpu() if interpret is None else interpret
    impl = _impl_default(impl)
    shape = tuple(shape)
    if isinstance(step_dec, (int, float)):
        return _fused_decode_scalar(word, s_sum, bias_sum, float(step_dec),
                                    offset, bits, shape, impl, interpret)
    return _fused_decode_percoord(word, s_sum, bias_sum, step_dec, offset,
                                  bits, shape, impl, interpret)


@functools.partial(jax.jit, static_argnames=("sigma", "interpret"))
def layered_encode(x, u, layer, sigma: float, interpret: bool | None = None):
    interpret = _on_cpu() if interpret is None else interpret
    xr = _pad_rows(x, 1)
    ur = _pad_rows(u, 1)
    lr = _pad_rows(jnp.maximum(layer, 1e-30), 1)
    m = le.layered_encode(
        xr.reshape(-1, LANES), ur.reshape(-1, LANES), lr.reshape(-1, LANES),
        sigma, interpret=interpret,
    )
    return m.reshape(-1)[: x.size].reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("sigma", "interpret"))
def layered_decode(m, u, layer, sigma: float, interpret: bool | None = None):
    interpret = _on_cpu() if interpret is None else interpret
    mr = _pad_rows(m, 1)
    ur = _pad_rows(u, 1)
    lr = _pad_rows(jnp.maximum(layer, 1e-30), 1)
    y = le.layered_decode(
        mr.reshape(-1, LANES).astype(jnp.int32), ur.reshape(-1, LANES),
        lr.reshape(-1, LANES), sigma, interpret=interpret,
    )
    return y.reshape(-1)[: m.size].reshape(m.shape)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """q (B, T, H, D), k/v (B, S, HK, D); GQA via KV-head repetition."""
    interpret = _on_cpu() if interpret is None else interpret
    B, T, H, D = q.shape
    S, HK = k.shape[1], k.shape[2]
    if HK != H:
        rep = H // HK
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # pad sequence dims to block multiples (padded KEYS are masked inside
    # the kernel via the col < S bound; padded V rows must be zeros so
    # 0-probability x garbage never produces NaN)
    Tp = -(-T // bq) * bq
    Sp = -(-S // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    qf = qp.transpose(0, 2, 1, 3).reshape(B * H, Tp, D)
    kf = kp.transpose(0, 2, 1, 3).reshape(B * H, Sp, D)
    vf = vp.transpose(0, 2, 1, 3).reshape(B * H, Sp, D)
    o = fa.flash_attention_tpu(qf, kf, vf, causal=causal, bq=bq, bk=bk,
                               kv_len=S, interpret=interpret)
    return o.reshape(B, H, Tp, D)[:, :, :T].transpose(0, 2, 1, 3)
