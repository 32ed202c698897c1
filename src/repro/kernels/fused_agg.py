"""Pallas TPU kernels: fused homomorphic encode / decode for the
aggregate AINQ mechanisms (aggregate_gaussian, aggregate_laplace,
irwin_hall).

These generalize ``dither_pack.py`` from its fixed scalar-step signed
form to the mechanisms' geometry:

  * the quantization step may be PER-COORDINATE (the aggregate
    mechanisms' shared DECOMPOSE draw gives step = A * w with A an
    array in per_coord mode) or a compile-time scalar (Irwin-Hall);
  * fields are packed UNSIGNED with bias m_max so that int32 words sum
    homomorphically across clients (see ``repro.core.packing``): the
    cross-pod psum carries b-bit payloads, b = ceil(log2(range));
  * decode fuses unpack + bias/dither subtraction + rescale + the
    mechanism's additive offset (B * sigma) in the same VMEM pass.

Encode, one pass per (rows x 128) tile:

    m      = clamp(floor(x / step + s + 1/2), -m_max, m_max)
    word_c = sum_j (m[j, c] + m_max) << (bits * j)     G = 32//bits

Decode (word_sum = psum of packed words of r messages, bias_sum =
r * m_max, s_sum = dither sum):

    u_j = (word_sum >> (bits * j)) & mask              (unsigned)
    y   = (float(u - bias_sum) - s_sum) * step_dec [+ offset]

The bias comes off in int32, before the float conversion: a biased
field sum near 2^24 has an f32 spacing of 1, so folding the dither into
one ``s_sum + bias_sum`` float would round the dither to an integer.

Layout matches dither_pack.py: (R, G, 128) tiles in VMEM, packing
reduces over the G axis; shapes padded to row multiples by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_R = 256  # rows (of 128-lane vectors) per tile
LANES = 128


def _quantize_pack(x, s, step, bits: int, m_max: int):
    g = max(32 // bits, 1)
    m = jnp.clip(jnp.floor(x / step + s + 0.5), float(-m_max), float(m_max))
    u = m.astype(jnp.int32) + m_max
    word = jnp.zeros((x.shape[0], LANES), jnp.int32)
    for j in range(g):  # static unroll over the pack group
        word = word | (u[:, j, :] << (bits * j))
    return word


def _unpack_affine(word, bias_sum, s_sum, step, offset, bits: int):
    g = max(32 // bits, 1)
    mask = (1 << bits) - 1
    outs = []
    for j in range(g):
        # arithmetic shift + mask extracts exact bits [b*j, b*(j+1)) even
        # when the top field occupies bit 31 of the summed word
        u = (word >> (bits * j)) & mask
        outs.append((u - bias_sum).astype(jnp.float32))
    m = jnp.stack(outs, axis=1)  # (R, G, 128) signed message sums
    y = (m - s_sum) * step
    return y if offset is None else y + offset


def _encode_kernel(*refs, step: float | None, bits: int, m_max: int):
    if step is None:
        x_ref, s_ref, t_ref, o_ref = refs
        st = t_ref[...]
    else:
        x_ref, s_ref, o_ref = refs
        st = step
    o_ref[...] = _quantize_pack(x_ref[...], s_ref[...], st, bits, m_max)


def _decode_kernel(*refs, step: float | None, has_offset: bool, bits: int):
    refs = list(refs)
    w_ref, b_ref, s_ref = refs[0], refs[1], refs[2]
    pos = 3
    if step is None:
        st = refs[pos][...]
        pos += 1
    else:
        st = step
    off = refs[pos][...] if has_offset else None
    o_ref = refs[-1]
    o_ref[...] = _unpack_affine(w_ref[...], b_ref[0, 0], s_ref[...], st, off,
                                bits)


def fused_encode(x, s, step, bits: int, m_max: int, *,
                 interpret: bool = False):
    """x, s: (R, G, 128) f32 with G = 32 // bits; ``step`` a python
    scalar or an (R, G, 128) array -> packed biased int32 words (R, 128).
    """
    R, G, L = x.shape
    assert G == max(32 // bits, 1) and L == LANES, (x.shape, bits)
    bm = min(BLOCK_R, R)
    grid = (pl.cdiv(R, bm),)
    spec3 = pl.BlockSpec((bm, G, LANES), lambda i: (i, 0, 0))
    scalar = isinstance(step, (int, float))
    in_specs = [spec3, spec3] + ([] if scalar else [spec3])
    args = (x, s) if scalar else (x, s, step)
    return pl.pallas_call(
        functools.partial(
            _encode_kernel, step=float(step) if scalar else None,
            bits=bits, m_max=m_max,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANES), jnp.int32),
        interpret=interpret,
    )(*args)


def fused_decode(word, bias_sum, s_sum, step, offset, bits: int, *,
                 interpret: bool = False):
    """Summed packed words (R, 128), the packing bias of the summed
    messages ``bias_sum`` = r * m_max ((1, 1) int32) and the dither sum
    s_sum (R, G, 128) -> f32 (R, G, 128).  ``step`` is the DECODE step
    (mechanism step / n); ``offset`` is the additive shared offset
    (B * sigma) or None."""
    R, L = word.shape
    G = max(32 // bits, 1)
    bm = min(BLOCK_R, R)
    grid = (pl.cdiv(R, bm),)
    spec3 = pl.BlockSpec((bm, G, LANES), lambda i: (i, 0, 0))
    scalar = isinstance(step, (int, float))
    in_specs = [pl.BlockSpec((bm, LANES), lambda i: (i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM), spec3]
    args = [word, bias_sum, s_sum]
    if not scalar:
        in_specs.append(spec3)
        args.append(step)
    if offset is not None:
        in_specs.append(spec3)
        args.append(offset)
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, step=float(step) if scalar else None,
            has_offset=offset is not None, bits=bits,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=spec3,
        out_shape=jax.ShapeDtypeStruct((R, G, LANES), jnp.float32),
        interpret=interpret,
    )(*args)
