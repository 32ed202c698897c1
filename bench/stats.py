"""Kolmogorov-Smirnov helpers for the codec's exact error law.

``ks_statistic``, ``norm_cdf`` and ``ks_threshold`` are copies of the
program's ``tests/helpers.py`` (host, float64), kept here so that the
yardstick cannot move.  ``ks_normal_device`` computes the same statistic
on the device, for the tens of millions of errors a codec run compares.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def ks_statistic(samples, cdf):
    """Two-sided KS statistic of samples against a cdf callable."""
    s = np.sort(np.asarray(samples, np.float64))
    n = len(s)
    c = cdf(s)
    return max(
        float(np.max(np.abs(c - np.arange(1, n + 1) / n))),
        float(np.max(np.abs(c - np.arange(n) / n))),
    )


def norm_cdf(x, sigma=1.0):
    return 0.5 * (1.0 + np.vectorize(math.erf)(np.asarray(x) / (sigma * math.sqrt(2))))


def ks_threshold(n, alpha_like=0.001):
    return 1.95 / np.sqrt(n)


@jax.jit
def _ks_sorted(err, sigma):
    s = jnp.sort(err)
    n = s.shape[0]
    c = jax.scipy.special.ndtr(s / sigma)
    i = jnp.arange(n, dtype=jnp.float32)
    return jnp.maximum(jnp.max(jnp.abs(c - (i + 1.0) / n)),
                       jnp.max(jnp.abs(c - i / n)))


def ks_normal_device(err, sigma: float) -> float:
    """KS statistic of a flat f32 device array against N(0, sigma^2)."""
    return float(_ks_sorted(err, jnp.float32(sigma)))
