"""DECOMPOSEUNIF / DECOMPOSE (paper Algorithms 1-2, Appendix A.2/A.4).

Given the Irwin-Hall noise P that the homomorphic dithering fleet
produces, these algorithms draw (A, B) from a coupling in Pi_{A,B}(P, Q)
so that  A * Z + B ~ Q  for Z ~ P (unit-variance Irwin-Hall here,
Q = N(0,1)).  The aggregate Q mechanism then runs the Irwin-Hall
mechanism with step scaled by A and output shifted by B.

Implementation notes (see DESIGN.md "hardware adaptation"):
  * both algorithms are rejection loops with O(sqrt(n)) expected
    iterations; we implement them as ``lax.while_loop``s so they jit
    and vmap (per-coordinate mode) cleanly;
  * the Irwin-Hall pdf / derivative / inverse come from the float64 FFT
    grids in ``irwin_hall.py``;
  * every table is read through ``interp``: jnp.interp's interval and
    formula, with the interval found without a binary search (arithmetic
    on a power-of-two grid, else a two-level count of the knots <= x) and
    at most two gathers, since each step of a binary search is a gather
    over all lanes of the vmapped draw;
  * Algorithm 1 as printed omits the scale update ``a <- a (1/2 - s)``
    (the recursion re-expresses U(s, 1/2) as an affine image of
    U(-1/2, 1/2)); Algorithm 2 line 9 normalizes f to [-1/2, 1/2],
    which for a density is  f~(x) = L f(L x).  Both fixed here and
    verified by distribution tests (A Z + B ~ Q, KS).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.irwin_hall import NormalizedIrwinHall

__all__ = [
    "gaussian_ih_lambda",
    "laplace_ih_lambda",
    "decompose_unif",
    "decompose_gaussian",
    "DecomposeTables",
    "Lookup",
    "lookup_table",
    "interval",
    "interp",
    "gaussian_tables",
    "laplace_tables",
]

_MAX_ITERS = 100_000  # hard cap; P(hit) ~ (1 - 1/f(0))^cap, astronomically small


def _norm_pdf64(x):
    return np.exp(-0.5 * x * x) / math.sqrt(2.0 * math.pi)


def _laplace_pdf64(x):
    # unit-variance Laplace: b = 1/sqrt(2)
    b = 1.0 / math.sqrt(2.0)
    return np.exp(-np.abs(x) / b) / (2.0 * b)


_TARGET_PDFS = {"gaussian": _norm_pdf64, "laplace": _laplace_pdf64}
_TARGET_TAILS = {"gaussian": 9.5, "laplace": 16.0}


def _target_pdf_prime(family: str, x: np.ndarray) -> np.ndarray:
    if family == "gaussian":
        return -x * _norm_pdf64(x)
    b = 1.0 / math.sqrt(2.0)
    return -np.sign(x) / b * _laplace_pdf64(x)


@functools.lru_cache(maxsize=64)
def _lambda_and_psi_grid(
    n: int, family: str = "gaussian"
) -> Tuple[float, np.ndarray, np.ndarray]:
    """lambda = inf_{x>0} g'(x)/f'(x) and a grid of psi~(x) = g - lambda f.

    Unit scale: g = the unit-variance target pdf (Gaussian or Laplace),
    f = unit-variance Irwin-Hall(n).  Returns (lambda, xs, psi(xs)) with
    xs on [0, xmax], psi decreasing.
    """
    ih = NormalizedIrwinHall(n)
    g_pdf = _TARGET_PDFS[family]
    scale = ih.unit_scale  # X_unit = scale * X_norm
    if n <= 2:
        lam = 0.0  # paper's choice for n <= 2
    else:
        xs_n = ih._xs64[1:]  # avoid the x=0 point (0/0)
        f_prime = ih._dfs64[1:] / scale**2  # d f_unit / dx at xs_n*scale
        x_unit = xs_n * scale
        g_prime = _target_pdf_prime(family, x_unit)
        mask = f_prime < -1e-12
        ratio = g_prime[mask] / f_prime[mask]
        lam = float(np.clip(np.min(ratio), 0.0, 1.0)) if mask.any() else 0.0
    # psi~ = g - lam * f_unit on [0, xmax]; decreasing by construction.
    xmax = max(math.sqrt(3.0 * n), _TARGET_TAILS[family])
    xs = np.linspace(0.0, xmax, 16385)
    f_unit = np.interp(xs / scale, ih._xs64, ih._fs64, right=0.0) / scale
    psi = np.maximum(g_pdf(xs) - lam * f_unit, 0.0)
    psi = np.minimum.accumulate(psi)  # enforce monotone (grid noise guard)
    return lam, xs, psi


def gaussian_ih_lambda(n: int) -> float:
    """Mixture weight lambda of the exact-IH component (Sec. 4.4 step 2)."""
    return _lambda_and_psi_grid(n)[0]


def laplace_ih_lambda(n: int) -> float:
    return _lambda_and_psi_grid(n, "laplace")[0]


_ROW = 128  # knots per row of a non-uniform table: one lane row
_STRIDE = _ROW - 1  # rows overlap by one knot, so an interval lies in one row


class Lookup(NamedTuple):
    """``jnp.interp(x, xp, fp, right=right)`` prepared on the host.

    ``x_step`` is xp's spacing when xp[k] = (x_first + k) * x_step exactly
    with x_step a power of two: the interval is then arithmetic, and so
    are its ends.  Otherwise (``x_step`` 0.0) the interval is a count of
    the knots <= x in two levels: over the first knot of each row of
    ``rows``, then over the one row that gives (one gather), which also
    holds both ends of the interval.  Likewise fp's ends are arithmetic
    on such a grid (``f_step``, which may be negative) and otherwise come
    from ``ends`` row j = (fp[j], fp[j+1]) in one gather.  numpy and
    Python values, as in ``DecomposeTables``."""

    x_step: float
    x_first: int
    f_step: float
    f_first: int
    knots: np.ndarray  # xp, float32
    rows: np.ndarray  # [R, 128]: row r = xp[127 r : 127 r + 128], +inf padded
    ends: np.ndarray  # [T-1, 2]: (fp[j], fp[j+1])
    left: float  # fp[0], for x < xp[0]
    right: float  # for x > xp[-1]


def _pow2_grid(a: np.ndarray) -> Tuple[float, int]:
    """(step, first) when a[k] == (first + k) * step exactly, |step| a
    power of two; else (0.0, 0)."""
    step = float(a[1]) - float(a[0])
    if step == 0.0 or math.frexp(step)[0] not in (0.5, -0.5):
        return 0.0, 0
    first = float(a[0]) / step
    if first != int(first) or abs(first) + len(a) >= 2**24:
        return 0.0, 0
    grid = (int(first) + np.arange(len(a), dtype=np.float64)) * step
    if not np.array_equal(a.astype(np.float64), grid):
        return 0.0, 0
    return step, int(first)


def lookup_table(xp, fp, right=None) -> Lookup:
    """The ``Lookup`` of ``jnp.interp(., xp, fp, right=right)`` (xp sorted)."""
    xp = np.asarray(xp, np.float32)
    fp = np.asarray(fp, np.float32)
    x_step, x_first = _pow2_grid(xp)
    f_step, f_first = _pow2_grid(fp)
    nrows = 0 if x_step else (len(xp) - 2) // _STRIDE + 1
    padded = np.concatenate(
        [xp, np.full(_STRIDE * nrows + 1, np.inf, np.float32)])
    at = _STRIDE * np.arange(nrows)[:, None] + np.arange(_ROW)
    return Lookup(
        x_step=x_step,
        x_first=x_first,
        f_step=f_step,
        f_first=f_first,
        knots=xp,
        rows=padded[at],
        ends=np.zeros((0, 2), np.float32) if f_step
        else np.stack([fp[:-1], fp[1:]], axis=1),
        left=float(fp[0]),
        right=float(fp[-1] if right is None else right),
    )


def _gather(table: np.ndarray, i):
    # promise_in_bounds: plain indexing's bounds handling makes each gather
    # about 4 times slower on a TPU v5e; every index here is clipped
    return jnp.asarray(table).at[i].get(mode="promise_in_bounds")


def _counted(table: Lookup, x):
    """(i, c, row) on a non-uniform xp: jnp.interp's interval i, and the
    row of ``rows`` that holds xp[i-1] and xp[i] at lanes c-1 and c."""
    T, R = len(table.knots), len(table.rows)
    # Every knot before the last row whose first knot is <= x is <= x,
    # and none after that row's last knot (the next row's first), since xp
    # is sorted, ties and duplicates included.  Knot axis first: it fuses
    # into the sum.
    heads = table.rows[:, 0].reshape((R,) + (1,) * jnp.ndim(x))
    r = jnp.clip(jnp.sum(heads <= x, axis=0, dtype=jnp.int32) - 1, 0, R - 1)
    row = _gather(table.rows, r)
    i = r * _STRIDE + jnp.sum(row <= x[..., None], axis=-1, dtype=jnp.int32)
    i = jnp.clip(i, 1, T - 1)
    return i, i - r * _STRIDE, row


def _grid_index(table: Lookup, x):
    T = len(table.knots)
    # floor(x / step) is exact; clip first so that int32 cannot overflow
    k = jnp.clip(jnp.floor(x * (1.0 / table.x_step)), table.x_first - 1,
                 table.x_first + T)
    return jnp.clip(k.astype(jnp.int32) - table.x_first + 1, 1, T - 1)


def interval(table: Lookup, x):
    """jnp.interp's interval: clip(searchsorted(xp, x, "right"), 1, T-1)."""
    if table.x_step:
        return _grid_index(table, x)
    return _counted(table, x)[0]


def _lane(row, c):
    """row[..., c] as a select and a sum over the lanes, not a gather."""
    return jnp.sum(jnp.where(jnp.arange(_ROW) == c[..., None], row, 0.0),
                   axis=-1)


def interp(table: Lookup, x):
    """``jnp.interp(x, xp, fp, right=...)`` of ``table``, to rounding."""
    if table.x_step:
        i = _grid_index(table, x)
        x0 = (i - 1 + table.x_first).astype(jnp.float32) * table.x_step
        dx = jnp.float32(table.x_step)
    else:
        i, c, row = _counted(table, x)
        x0 = _lane(row, c - 1)
        dx = _lane(row, c) - x0
    if table.f_step:
        f0 = (i - 1 + table.f_first).astype(jnp.float32) * table.f_step
        f1 = (i + table.f_first).astype(jnp.float32) * table.f_step
    else:
        pair = _gather(table.ends, i - 1)
        f0, f1 = pair[..., 0], pair[..., 1]
    # jnp.interp's formula; dx0 marks duplicate knots
    dx0 = jnp.abs(dx) <= np.spacing(np.finfo(np.float32).eps)
    f = jnp.where(dx0, f0, f0 + ((x - x0) / jnp.where(dx0, 1.0, dx)) * (f1 - f0))
    f = jnp.where(x < table.knots[0], table.left, f)
    return jnp.where(x > table.knots[-1], table.right, f)


class DecomposeTables(NamedTuple):
    """Host-resident (numpy) tables for the jittable decompose sampler.

    Kept as numpy on purpose: the constructors are lru_cached and may
    first run inside an arbitrary trace (jit / vmap / shard_map) — jnp
    constants built there would poison the cache with leaked tracers
    (``ensure_compile_time_eval`` does not escape a ShardMapTrace on
    jax<=0.4.x).  numpy constants are trace-proof and are promoted to
    device constants at use."""

    n: int
    family: str
    lam: float
    L: float  # support width of unit-variance IH = 2 sqrt(3n)
    peak_norm: float  # f~(0) of the normalized ([-1/2,1/2]) IH
    norm_pdf: Lookup  # f~(|x|) on the [0, 1/2] grid, 0 beyond
    norm_inv: Lookup  # f~^{-1}(y): increasing f~ values (reversed) -> x
    psi_inv: Lookup  # psi~^{-1}(v): increasing psi values (reversed) -> x


@functools.lru_cache(maxsize=64)
def gaussian_tables(n: int) -> DecomposeTables:
    return _tables_eager(n, "gaussian")


@functools.lru_cache(maxsize=64)
def laplace_tables(n: int) -> DecomposeTables:
    """Aggregate LAPLACE mechanism tables — the paper's "e.g. Gaussian or
    Laplace" generality: decompose a unit-variance Laplace into a mixture
    of shifted/scaled Irwin-Hall."""
    return _tables_eager(n, "laplace")


def _tables_eager(n: int, family: str) -> DecomposeTables:
    ih = NormalizedIrwinHall(n)
    lam, psi_xs, psi = _lambda_and_psi_grid(n, family)
    return DecomposeTables(
        n=n,
        family=family,
        lam=float(lam),
        L=2.0 * math.sqrt(3.0 * n),
        peak_norm=float(ih._fs64[0]),
        norm_pdf=lookup_table(ih._xs64, ih._fs64, right=0.0),
        norm_inv=lookup_table(ih._fs64[::-1], ih._xs64[::-1]),
        psi_inv=lookup_table(psi[::-1], psi_xs[::-1]),
    )


class _UnifState(NamedTuple):
    a: jnp.ndarray
    b: jnp.ndarray
    done: jnp.ndarray
    it: jnp.ndarray
    key: jnp.ndarray


def decompose_unif(tables: DecomposeTables, key) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Algorithm DECOMPOSEUNIF: (a, b) with a*X~ + b ~ U(-1/2, 1/2),
    X~ ~ normalized Irwin-Hall on [-1/2, 1/2]."""

    f0 = tables.peak_norm

    def cond(st: _UnifState):
        return jnp.logical_and(~st.done, st.it < _MAX_ITERS)

    def body(st: _UnifState):
        key, k1, k2 = jax.random.split(st.key, 3)
        u = jax.random.uniform(k1, minval=-0.5, maxval=0.5)
        v = jax.random.uniform(k2)
        accept = v <= interp(tables.norm_pdf, jnp.abs(u)) / f0
        s = interp(tables.norm_inv, v * f0)  # positive edge of {f~ < v f0}
        b_new = st.b + st.a * jnp.sign(u) * 0.5 * (s + 0.5)
        a_new = st.a * (0.5 - s)
        return _UnifState(
            a=jnp.where(accept, st.a, a_new),
            b=jnp.where(accept, st.b, b_new),
            done=accept,
            it=st.it + 1,
            key=key,
        )

    init = _UnifState(
        a=jnp.float32(1.0),
        b=jnp.float32(0.0),
        done=jnp.array(False),
        it=jnp.int32(0),
        key=key,
    )
    out = jax.lax.while_loop(cond, body, init)
    return out.a, out.b


def decompose_gaussian(tables: DecomposeTables, key) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Algorithm DECOMPOSE for Q = N(0,1), P = unit-variance IH(n).

    Returns (A, B) such that A * Z_unit + B ~ N(0, 1) where
    Z_unit ~ IH(n, 0, 1).  vmap over ``key`` for per-coordinate draws.
    """
    kx, kv, ku = jax.random.split(key, 3)
    if tables.family == "laplace":
        b = 1.0 / math.sqrt(2.0)
        x = b * jax.random.laplace(kx)
        g_x = jnp.exp(-jnp.abs(x) / b) / (2.0 * b)
    else:
        x = jax.random.normal(kx)
        g_x = jnp.exp(-0.5 * x * x) / math.sqrt(2.0 * math.pi)
    v = jax.random.uniform(kv) * g_x
    scale = tables.L / 1.0  # unit support width; X_unit = L * X_norm
    f_unit = interp(tables.norm_pdf, jnp.abs(x) / scale) / scale
    take_f = v > g_x - tables.lam * f_unit  # exact-IH component (A,B)=(1,0)
    s = interp(tables.psi_inv, v)  # psi~^{-1}(v)
    a_u, b_u = decompose_unif(tables, ku)
    A = 2.0 * a_u * s / tables.L
    B = 2.0 * b_u * s
    return (
        jnp.where(take_f, 1.0, A).astype(jnp.float32),
        jnp.where(take_f, 0.0, B).astype(jnp.float32),
    )
