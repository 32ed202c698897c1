"""Aggregate Q mechanism (paper Def. 8) with Q = Gaussian (Sec. 4.4).

Homomorphic AND exactly Gaussian: global shared randomness T = (A, B)
is drawn by DECOMPOSE, then every client runs subtractive dithering with
step A*w (w = 2 sigma sqrt(3n)); the server decodes the *sum* of the
integer descriptions:

    M_i = round(x_i / (A w) + S_i)
    Y   = (A w / n) (sum_i M_i - sum_i S_i) + B sigma
    Y - mean(x)  ~  N(0, sigma^2)       (exactly; Prop. 3)

Two vectorization modes over R^d (DESIGN.md "assumptions changed"):
  * per_coord=True  : one (A, B) per coordinate (paper-faithful i.i.d.
                      noise; required for DP).
  * per_coord=False : one (A, B) per tensor; each coordinate's marginal
                      noise is still exactly N(0, sigma^2) but
                      coordinates are dependent. Cheaper shared RNG.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import debug
from repro.core import dither
from repro.core.packing import PackGeometry, geometry_for_bits
from repro.core.decompose import (
    DecomposeTables,
    decompose_gaussian,
    gaussian_tables,
    laplace_tables,
)

__all__ = ["AggregateGaussianMechanism", "AggGaussShared"]

DECOMPOSE_BATCH = 1 << 20  # coordinates per vmapped per-coordinate draw


class AggGaussShared(NamedTuple):
    """Global shared randomness T = (A, B) (scalar or per-coordinate)."""

    A: jnp.ndarray
    B: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class AggregateGaussianMechanism:
    """Aggregate AINQ mechanism: noise exactly ~ Q with std sigma, for
    Q the target ``family`` (the paper's "e.g. Gaussian or Laplace").

    Only the DECOMPOSE target changes between families: (A, B) are drawn
    so that A * IH + B follows the unit-variance target, and everything
    downstream (dither step A*w, summed decode, bit accounting) is
    target-agnostic.
    """

    n: int
    sigma: float
    per_coord: bool = True
    family: str = "gaussian"  # gaussian | laplace

    homomorphic = True

    def __post_init__(self):
        if self.family not in ("gaussian", "laplace"):
            raise ValueError(f"unknown aggregate family {self.family!r}")

    @property
    def name(self) -> str:
        return f"aggregate_{self.family}"

    @property
    def exact_gaussian(self) -> bool:
        return self.family == "gaussian"

    @property
    def w(self) -> float:
        return 2.0 * self.sigma * math.sqrt(3.0 * self.n)

    @property
    def tables(self) -> DecomposeTables:
        if self.family == "laplace":
            return laplace_tables(self.n)
        return gaussian_tables(self.n)

    # --- shared randomness -----------------------------------------------
    def global_randomness(self, key, shape=(), *, a_min=0.0) -> AggGaussShared:
        """T = (A, B); every client and the server derive this from the
        common seed (replicated computation in SPMD).

        ``a_min`` clamps the step scale A from below: the decompose law
        puts ~1e-3 mass on A small enough that messages x/(A w) overflow
        the int32 psum payload (error blow-ups of 100+ sigma observed).
        Callers set a_min = t_range * n / (w * 2^30) so |sum_i M_i| stays
        within int32; the induced deviation from the exact error law is
        P[A < a_min] in total variation (clamped draws keep the exact
        subtractive-dither uniform error at step a_min*w, shifted by the
        jointly drawn B sigma — bounded, just not exactly Gaussian).
        """
        tables = self.tables
        if self.per_coord and shape:
            flat = math.prod(shape)
            keys = jax.random.split(key, flat)
            if debug.active():
                # checkify cannot functionalize batched while-loops, so
                # under the sanitizer run the rejection sampler as a
                # sequential scan instead of a vmap (debug-only cost)
                A, B = jax.lax.map(
                    lambda k: decompose_gaussian(tables, k), keys)
            else:
                # vmapped in batches: one vmap over a whole embedding
                # leaf holds the rejection loop's per-lane keys and
                # splits for every coordinate at once (GBs of HBM);
                # lanes are independent, so batching is bit-identical
                A, B = jax.lax.map(
                    lambda k: decompose_gaussian(tables, k), keys,
                    batch_size=DECOMPOSE_BATCH)
            A, B = A.reshape(shape), B.reshape(shape)
        else:
            A, B = decompose_gaussian(tables, key)
            A = jnp.broadcast_to(A, shape)
            B = jnp.broadcast_to(B, shape)
        if debug.active():
            # the exact-error claim degrades by P[A < a_min] in total
            # variation; past this bound the geometry is mis-sized
            debug.check(
                jnp.mean((A < a_min).astype(jnp.float32))
                <= debug.A_CLAMP_MASS_BOUND,
                "global_randomness: A-clamp mass exceeds "
                f"{debug.A_CLAMP_MASS_BOUND} (geometry too narrow for "
                "clip/sigma)")
        return AggGaussShared(jnp.maximum(A, a_min), B)

    def a_min_for_range(self, t_range, *, msg_bits: int = 30):
        """Smallest safe A for inputs |x_i| <= t_range / 2: keeps the
        *summed* message within a 2^msg_bits+ budget (int32 psum)."""
        return t_range * self.n / (self.w * float(2**msg_bits))

    # --- packed-collective geometry ---------------------------------------
    def pack_geometry(self, bits: int) -> PackGeometry:
        """Geometry of the true-bit-width packed collective: ``bits``-wide
        unsigned fields whose n-fold sum cannot carry (see core.packing).
        The step scale A must be clamped at ``a_min_for_geometry`` so the
        natural message range fits the field clamp."""
        return geometry_for_bits(bits, self.n)

    def a_min_for_geometry(self, clip: float, geom: PackGeometry):
        """Smallest A whose messages floor(x/(A w) + s + 1/2) stay within
        [-m_max, m_max] for |x| <= clip: |m| <= clip/(A w) + 1 <= m_max."""
        return clip / ((geom.m_max - 1) * self.w)

    def client_randomness(self, key, shape=(), dtype=jnp.float32):
        """S_i ~ U(-1/2,1/2) per coordinate; key = fold_in(round_key, i)."""
        return dither.dither_noise(key, shape, dtype)

    # --- encode / decode ---------------------------------------------------
    def encode(self, x_i, s_i, t: AggGaussShared):
        return dither.dither_encode(x_i, t.A * self.w, s_i)

    def decode_sum(self, m_sum, s_sum, t: AggGaussShared, *, dtype=jnp.float32):
        step = (t.A * self.w / self.n).astype(dtype)
        return (m_sum.astype(dtype) - s_sum.astype(dtype)) * step + (
            t.B * self.sigma
        ).astype(dtype)

    # --- communication accounting -------------------------------------------
    def bits_fixed_given_A(self, t_range: float, A) -> jnp.ndarray:
        """ceil(log2(t/(w A) + 3)) bits per coordinate, conditional on A
        (Sec. 4.5), for inputs |x_i| <= t_range/2."""
        return jnp.ceil(jnp.log2(t_range / (self.w * jnp.abs(A)) + 3.0))
