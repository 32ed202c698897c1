"""Token batches for the training cells, made on the device from the seed.

``lm_batch_scan`` is a copy of the program's ``data/synthetic.lm_batch``
(learnable affine-mod chains t[k+1] = (3 t[k] + 7) mod V from a random
start per row), kept here so that a change to the program cannot move the
benchmark's data.  ``lm_batch`` makes the same tokens without the
sequential scan: t[k] = (3^k t[0] + 7 (3^k - 1) / 2) mod V, with the
powers taken once on the host and the product split so that int32 holds
it; ``tests/test_synthetic.py`` checks that the two agree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

MULT, ADD = 3, 7
_SPLIT = 512  # t0 = hi * 512 + lo keeps every product below 2^31


def _chain(key, batch: int, seq: int, vocab: int, mult: int = MULT,
           add: int = ADD):
    t0 = jax.random.randint(key, (batch, 1), 0, vocab)

    def body(t, _):
        nxt = (mult * t + add) % vocab
        return nxt, nxt

    _, rest = jax.lax.scan(body, t0, None, length=seq - 1)
    return jnp.concatenate([t0, rest.squeeze(-1).T.reshape(batch, seq - 1)],
                           axis=1)


def lm_batch_scan(key, batch: int, seq: int, vocab: int):
    """The program's generator, step key already folded in."""
    return _chain(key, batch, seq, vocab)


@functools.lru_cache(maxsize=8)
def _affine_powers(seq: int, vocab: int):
    p = np.empty(seq, np.int64)
    o = np.empty(seq, np.int64)
    p[0], o[0] = 1, 0
    for k in range(1, seq):
        p[k] = (MULT * p[k - 1]) % vocab
        o[k] = (MULT * o[k - 1] + ADD) % vocab
    return p.astype(np.int32), o.astype(np.int32)


def lm_batch(key, batch: int, seq: int, vocab: int):
    """Same tokens as ``lm_batch_scan``, all positions at once."""
    if vocab * _SPLIT >= 2**31:
        raise ValueError(f"vocab {vocab} too large for the int32 split")
    p, o = _affine_powers(seq, vocab)
    p, o = jnp.asarray(p)[None, :], jnp.asarray(o)[None, :]
    t0 = jax.random.randint(key, (batch, 1), 0, vocab)
    hi, lo = t0 // _SPLIT, t0 % _SPLIT
    t = ((p * hi) % vocab) * _SPLIT % vocab
    t = (t + (p * lo) % vocab + o) % vocab
    return t.astype(jnp.int32)


def step_key(seed_key, step: int):
    return jax.random.fold_in(seed_key, step)
