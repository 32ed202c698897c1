"""The DECOMPOSE tables' lookups against jnp.interp.

``decompose.interp`` finds jnp.interp's interval without a binary search
and fetches its ends with at most two gathers.  These tests hold it to jnp.interp:
the interval index exactly, the value to 2 float32 ULP, and the draw
(A, B) against the jnp.interp version of DECOMPOSE kept below as the
oracle.  A structural test keeps the search and the gathers out of the
compiled per-coordinate draw.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decompose
from repro.core.decompose import interp, interval, lookup_table
from repro.core.irwin_hall import NormalizedIrwinHall

NS = [1, 2, 3, 4, 8]
FAMILIES = {"gaussian": decompose.gaussian_tables,
            "laplace": decompose.laplace_tables}
TABLES = ["norm_pdf", "norm_inv", "psi_inv"]
ULPS = 2


def _oracle_tables(n, family):
    """The (xp, fp, right) of each table as DECOMPOSE passed them to
    jnp.interp, built here from the same float64 grids."""
    ih = NormalizedIrwinHall(n)
    _, psi_xs, psi = decompose._lambda_and_psi_grid(n, family)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    return {
        "norm_pdf": (f32(ih._xs64), f32(ih._fs64), 0.0),
        "norm_inv": (f32(ih._fs64[::-1]), f32(ih._xs64[::-1]), None),
        "psi_inv": (f32(psi[::-1]), f32(psi_xs[::-1]), None),
    }


def _points(xp, seed=0):
    """Random points, every knot and its float32 neighbours, 0 and points
    past either end."""
    rng = np.random.default_rng(seed)
    lo, hi = float(xp[0]), float(xp[-1])
    span = hi - lo
    pts = [
        rng.uniform(lo - 0.05 * span, hi + 0.05 * span, 4096),
        xp,
        np.nextafter(xp, np.float32(-np.inf)),
        np.nextafter(xp, np.float32(np.inf)),
        [0.0, hi + span, 2.0 * abs(hi) + 1.0, 1e30, -1e30],
    ]
    return np.concatenate([np.asarray(p, np.float32) for p in pts])


def _within_ulps(a, b, ulps=ULPS):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = np.spacing(np.maximum(np.abs(a), np.abs(b)))
    return np.abs(a.astype(np.float64) - b) <= ulps * scale.astype(np.float64)


def _cases():
    cases = []
    for fam in FAMILIES:
        for n in NS:
            for name in TABLES:
                cases.append(pytest.param((fam, n, name),
                                          id=f"{fam}-n{n}-{name}"))
    dups = np.repeat(np.arange(100, dtype=np.float32), 7)  # across rows
    shifted = (5 + np.arange(300, dtype=np.float32)) * 0.25  # a grid
    cases.append(pytest.param((dups, np.sqrt(dups), None), id="duplicates"))
    cases.append(pytest.param((shifted, shifted**2, 0.0), id="shifted-grid"))
    return cases


def _table_and_oracle(case):
    if isinstance(case[0], str):
        fam, n, name = case
        return (getattr(FAMILIES[fam](n), name),
                *_oracle_tables(n, fam)[name])
    xp, fp, right = case
    return (lookup_table(xp, fp, right=right), xp, fp, right)


@pytest.mark.parametrize("case", _cases())
def test_lookup_matches_jnp_interp(case):
    table, xp, fp, right = _table_and_oracle(case)
    x = jnp.asarray(_points(xp))
    T = len(xp)
    want_i = jnp.clip(jnp.searchsorted(jnp.asarray(xp), x, side="right"),
                      1, T - 1)
    got_i = jax.jit(lambda z: interval(table, z))(x)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    # under vmap too: the draw calls it once per lane
    got_vi = jax.jit(jax.vmap(lambda z: interval(table, z)))(x)
    np.testing.assert_array_equal(np.asarray(got_vi), np.asarray(want_i))
    want = jax.jit(lambda z: jnp.interp(z, xp, fp, right=right))(x)
    got = jax.jit(lambda z: interp(table, z))(x)
    ok = _within_ulps(got, want)
    assert ok.all(), (np.asarray(x)[~ok][:5], np.asarray(got)[~ok][:5],
                      np.asarray(want)[~ok][:5])


def test_grid_is_found_from_the_table():
    """Only an exact power-of-two grid of xp or fp is read arithmetically."""
    for fam, make in FAMILIES.items():
        for n in NS:
            t = make(n)
            assert (t.norm_pdf.x_step, t.norm_pdf.x_first) == (2.0**-13, 0)
            assert t.psi_inv.x_step == 0.0
            # the inverse pdf's values are the grid's xs, k/8192 falling
            assert (t.norm_inv.f_step, t.norm_inv.f_first) == (-2.0**-13, -4096)
            # the triangle's pdf 2 - 4x is linear: its values are k/2048
            assert t.norm_inv.x_step == (2.0**-11 if n == 2 else 0.0)
            assert t.norm_pdf.f_step == (-2.0**-11 if n == 2 else 0.0)
            # psi~ is tabled on linspace(0, xmax, 16385): a grid for xmax 16
            assert t.psi_inv.f_step == (-2.0**-10 if fam == "laplace" else 0.0)
    shifted = (5 + np.arange(300)) * 0.25
    t = lookup_table(shifted, shifted)
    assert (t.x_step, t.x_first, t.f_step, t.f_first) == (0.25, 5, 0.25, 5)
    tenths = np.linspace(0.0, 1.0, 11)  # uniform, step not a power of two
    t = lookup_table(tenths, tenths)
    assert t.x_step == t.f_step == 0.0
    bent = np.arange(64) / 8.0
    bent[40] += 2.0**-10
    t = lookup_table(np.sort(bent), bent)
    assert t.x_step == t.f_step == 0.0


# --- the jnp.interp version of DECOMPOSE: the oracle -------------------


def _oracle_unif(tables, key):
    f0 = tables.peak_norm
    oracle = _oracle_tables(tables.n, tables.family)
    norm_xs, norm_fs, _ = oracle["norm_pdf"]
    inv_y, inv_x, _ = oracle["norm_inv"]

    def pdf(x):
        return jnp.interp(jnp.abs(x), norm_xs, norm_fs, right=0.0)

    def inv(y):
        return jnp.interp(y, inv_y, inv_x)

    def cond(st):
        return jnp.logical_and(~st[2], st[3] < decompose._MAX_ITERS)

    def body(st):
        a, b, _, it, key = st
        key, k1, k2 = jax.random.split(key, 3)
        u = jax.random.uniform(k1, minval=-0.5, maxval=0.5)
        v = jax.random.uniform(k2)
        accept = v <= pdf(u) / f0
        s = inv(v * f0)
        b_new = b + a * jnp.sign(u) * 0.5 * (s + 0.5)
        a_new = a * (0.5 - s)
        return (jnp.where(accept, a, a_new), jnp.where(accept, b, b_new),
                accept, it + 1, key)

    init = (jnp.float32(1.0), jnp.float32(0.0), jnp.array(False),
            jnp.int32(0), key)
    a, b, *_ = jax.lax.while_loop(cond, body, init)
    return a, b


def _oracle_gaussian(tables, key):
    kx, kv, ku = jax.random.split(key, 3)
    if tables.family == "laplace":
        b = 1.0 / math.sqrt(2.0)
        x = b * jax.random.laplace(kx)
        g_x = jnp.exp(-jnp.abs(x) / b) / (2.0 * b)
    else:
        x = jax.random.normal(kx)
        g_x = jnp.exp(-0.5 * x * x) / math.sqrt(2.0 * math.pi)
    v = jax.random.uniform(kv) * g_x
    oracle = _oracle_tables(tables.n, tables.family)
    norm_xs, norm_fs, _ = oracle["norm_pdf"]
    psi_inv_y, psi_inv_x, _ = oracle["psi_inv"]
    scale = tables.L
    f_unit = (
        jnp.interp(jnp.abs(x) / scale, norm_xs, norm_fs, right=0.0) / scale
    )
    take_f = v > g_x - tables.lam * f_unit
    s = jnp.interp(v, psi_inv_y, psi_inv_x)
    a_u, b_u = _oracle_unif(tables, ku)
    A = 2.0 * a_u * s / tables.L
    B = 2.0 * b_u * s
    return (jnp.where(take_f, 1.0, A).astype(jnp.float32),
            jnp.where(take_f, 0.0, B).astype(jnp.float32))


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("family", list(FAMILIES))
def test_draw_matches_jnp_interp_oracle(family, n):
    """(A, B) per lane within 2 ULP of the oracle's.  A lane whose
    ``take_f`` or ``accept`` sits exactly at its threshold may take the
    other branch on a 1-ULP difference; such lanes stay under 1 in 10^4."""
    tables = FAMILIES[family](n)
    keys = jax.random.split(jax.random.PRNGKey(1400 + n), 4096)
    got = jax.jit(jax.vmap(lambda k: decompose.decompose_gaussian(tables, k)))(keys)
    want = jax.jit(jax.vmap(lambda k: _oracle_gaussian(tables, k)))(keys)
    close = _within_ulps(got[0], want[0]) & _within_ulps(got[1], want[1])
    flipped = int((~close).sum())
    assert flipped <= 1e-4 * close.size, (flipped, close.size)


@pytest.mark.parametrize("n", [1, 4])
def test_percoord_draw_has_no_search(n):
    """The compiled per-coordinate draw holds no binary search and at
    most 8 gathers (4 lookups, at most 2 each; jnp.interp took 20)."""
    tables = decompose.gaussian_tables(n)
    keys = jax.random.split(jax.random.PRNGKey(0), 1024)
    draw = jax.jit(jax.vmap(lambda k: decompose.decompose_gaussian(tables, k)))
    text = draw.lower(keys).compile().as_text()
    assert not [ln for ln in text.splitlines()
                if re.search(r'op_name="[^"]*searchsorted', ln)]
    gathers = re.findall(r"= \S+ gather\(", text)
    assert 0 < len(gathers) <= 8, len(gathers)
