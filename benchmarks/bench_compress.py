"""Fused vs unfused compressed-aggregation codec benchmark (ISSUE 7
acceptance grid).

Grid: homomorphic mechanism x packed field width x tensor size, n
clients each.  Per cell it measures

  * encode / decode wall time of the fused codec (XLA-fused oracle and
    the Pallas kernel in interpret mode — on a real TPU the kernel path
    is the fast one; interpret mode only checks it, slowly) against the
    unfused reference path;
  * the collective payload: packed int32 words (32/group bits per
    coordinate) vs one int32 word per coordinate unfused;
  * fused-vs-unfused decode agreement on identical keys (the two paths
    clamp to the same geometry, so messages are bit-identical);
  * a KS test of the aggregated error against the mechanism's exact
    law.  For the aggregate mechanisms a narrow geometry clamps the
    DECOMPOSE step scale A at `a_min_for_geometry`, which distorts the
    law by exactly the clamped mass — recorded per cell as
    ``clamp_fraction`` so a failed KS on a clamp-limited cell is
    expected, not a bug (Irwin-Hall has no A and stays exact whenever
    its natural range fits the field).

Sigmas are chosen per (mechanism, bits) so the acceptance cells keep
the clamp mass negligible at the benchmarked widths.

    PYTHONPATH=src python -m benchmarks.bench_compress --out BENCH_compress.json
"""
from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dither
from repro.core.irwin_hall import NormalizedIrwinHall
from repro.dist import compress as dc
from repro.kernels import ops

MECHS = ("aggregate_gaussian", "aggregate_laplace", "irwin_hall")
BITS = (4, 8, 16)
SIZES = (1 << 16, 1 << 20)
CLIP = 1.0

# bits=4 fields can hold at most n=2 summed messages with m_max >= 2
N_FOR_BITS = {4: 2, 8: 4, 16: 4}

# per (mechanism, bits): sigma keeping the geometry's A-clamp mass (or
# the IH range clamp) small enough for the exact law at that width
SIGMAS = {
    ("aggregate_gaussian", 4): 0.5,
    ("aggregate_gaussian", 8): 0.25,
    ("aggregate_gaussian", 16): 0.1,
    ("aggregate_laplace", 4): 0.5,
    ("aggregate_laplace", 8): 0.25,
    ("aggregate_laplace", 16): 0.1,
    ("irwin_hall", 4): 0.11,
    ("irwin_hall", 8): 5e-3,
    ("irwin_hall", 16): 1e-4,
}

# the ISSUE acceptance cell: bits <= 8, size >= 2^20, payload <= 0.5x
ACCEPTANCE = ("irwin_hall", 8, 1 << 20)


def _ks_statistic(samples, cdf):
    s = np.sort(np.asarray(samples, np.float64))
    n = len(s)
    c = cdf(s)
    return max(
        float(np.max(np.abs(c - np.arange(1, n + 1) / n))),
        float(np.max(np.abs(c - np.arange(n) / n))),
    )


def _error_cdf(mechanism: str, sigma: float, n: int):
    if mechanism == "aggregate_gaussian":
        return lambda z: 0.5 * (
            1.0 + np.vectorize(math.erf)(np.asarray(z) / (sigma * math.sqrt(2)))
        )
    if mechanism == "aggregate_laplace":
        b = sigma / math.sqrt(2.0)
        return lambda z: np.where(
            np.asarray(z) < 0,
            0.5 * np.exp(np.asarray(z) / b),
            1 - 0.5 * np.exp(-np.asarray(z) / b),
        )
    # irwin_hall: trapezoid-integrate the normalized IH half-density
    ih = NormalizedIrwinHall(n)
    xs, fs = np.asarray(ih._xs64), np.asarray(ih._fs64)
    half = np.concatenate([[0.0], np.cumsum((fs[1:] + fs[:-1]) / 2 * np.diff(xs))])
    grid = np.concatenate([-xs[::-1], xs[1:]])
    cdfv = np.concatenate([0.5 - half[::-1], 0.5 + half[1:]])
    scale = sigma * math.sqrt(12 * n)
    return lambda z: np.interp(np.asarray(z) / scale, grid, cdfv)


def _time_s(fn, *args, reps: int = 3) -> float:
    jax.block_until_ready(fn(*args))  # compile outside the clock
    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def run_cell(mechanism: str, bits: int, size: int) -> dict:
    n = N_FOR_BITS[bits]
    sigma = SIGMAS[(mechanism, bits)]
    comp_f = dc.CompressionConfig(mechanism=mechanism, sigma=sigma,
                                  clip=CLIP, fused=True, msg_bits=bits)
    comp_u = dc.CompressionConfig(mechanism=mechanism, sigma=sigma,
                                  clip=CLIP, fused=False, msg_bits=bits)

    key = jax.random.PRNGKey(42)
    kt, ks, kx = jax.random.split(key, 3)
    xs = jax.random.uniform(kx, (n, size), minval=-CLIP, maxval=CLIP)
    step, offset, geom = dc._leaf_params(comp_f, n, kt, (size,))
    s_all = jax.vmap(lambda j: jax.random.fold_in(ks, j))(jnp.arange(n))
    ss = jax.vmap(lambda k: dither.dither_noise(k, (size,)))(s_all)
    s_sum = ss.sum(0)

    clamp_fraction = 0.0
    if mechanism != "irwin_hall":
        mech = dc._make_mech(comp_f, n)
        a_min = mech.a_min_for_geometry(CLIP, geom)
        clamp_fraction = float(
            jnp.mean((step / mech.w) <= a_min * (1 + 1e-6))
        )

    # ---- payload + correctness (full n-client aggregate) ----
    words = [np.asarray(dc.encode_leaf(xs[i], comp_f, step, ss[i], geom))
             for i in range(n)]
    word_sum = jnp.asarray(sum(w.astype(np.int64) for w in words)
                           .astype(np.int32))
    y_f = dc.decode_leaf_sum(word_sum, comp_f, n, n, step, offset, s_sum,
                             geom, (size,))
    m_u = [dc.encode_leaf(xs[i], comp_u, step, ss[i], geom)
           for i in range(n)]
    m_sum = sum(m.astype(jnp.int32) for m in m_u)
    y_u = dc.decode_leaf_sum(m_sum, comp_u, n, n, step, offset, s_sum,
                             geom, (size,))
    agree = float(jnp.max(jnp.abs(y_f - y_u)))

    err = np.asarray(y_f - xs.mean(0))
    ks_stat = _ks_statistic(err, _error_cdf(mechanism, sigma, n))
    ks_thr = 1.95 / math.sqrt(size)

    # ---- wall time (codec only; the shared draw is replicated/amortized)
    x0, s0 = xs[0], ss[0]
    enc_xla = lambda x, s: dc.encode_leaf(x, comp_f, step, s, geom)
    enc_pal = lambda x, s: ops.fused_pack_encode(
        x, s, step, geom.bits, geom.m_max, impl="pallas")
    enc_unf = jax.jit(
        lambda x, s: dc.encode_leaf(x, comp_u, step, s, geom))
    dec_xla = lambda w, sm: dc.decode_leaf_sum(
        w, comp_f, n, n, step, offset, sm, geom, (size,))
    dec_pal = lambda w, sm: ops.fused_unpack_decode(
        w, sm, n * geom.bias, step / n, offset, geom.bits,
        (size,), impl="pallas")
    dec_unf = jax.jit(lambda m, sm: dc.decode_leaf_sum(
        m, comp_u, n, n, step, offset, sm, geom, (size,)))

    encode_s = {
        "fused_xla": _time_s(enc_xla, x0, s0),
        "fused_pallas_interpret": _time_s(enc_pal, x0, s0),
        "unfused": _time_s(enc_unf, x0, s0),
    }
    decode_s = {
        "fused_xla": _time_s(dec_xla, word_sum, s_sum),
        "fused_pallas_interpret": _time_s(dec_pal, word_sum, s_sum),
        "unfused": _time_s(dec_unf, m_sum, s_sum),
    }

    payload_fused = 4 * geom.n_words(size)
    payload_unfused = 4 * size  # one int32 word per coordinate
    return {
        "mechanism": mechanism,
        "bits": bits,
        "size": size,
        "n": n,
        "sigma": sigma,
        "geom_bits": geom.bits,
        "m_max": geom.m_max,
        "group": geom.group,
        "payload_bytes_fused": payload_fused,
        "payload_bytes_unfused": payload_unfused,
        "payload_ratio": payload_fused / payload_unfused,
        "wire_bits_per_coord": dc.wire_bits_per_coord(comp_f, n, size),
        "encode_s": encode_s,
        "decode_s": decode_s,
        "fused_vs_unfused_max_dev": agree,
        "clamp_fraction": clamp_fraction,
        "ks": {
            "stat": ks_stat,
            "threshold": ks_thr,
            "n_samples": size,
            "pass": bool(ks_stat < ks_thr),
        },
    }


def run(emit) -> None:
    """benchmarks.run entry: the fast subset (2^16 tensors only)."""
    for mechanism in MECHS:
        for bits in BITS:
            c = run_cell(mechanism, bits, 1 << 16)
            tag = f"compress/{mechanism}_b{bits}"
            emit(f"{tag}_encode_fused_s", round(c["encode_s"]["fused_xla"], 6),
                 f"unfused_s={c['encode_s']['unfused']:.6f}"
                 f"|payload_ratio={c['payload_ratio']:.3f}")
            emit(f"{tag}_decode_fused_s", round(c["decode_s"]["fused_xla"], 6),
                 f"ks={c['ks']['stat']:.4f}"
                 f"|dev={c['fused_vs_unfused_max_dev']:.2e}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_compress.json")
    args = ap.parse_args()

    cells = []
    for mechanism in MECHS:
        for bits in BITS:
            for size in SIZES:
                c = run_cell(mechanism, bits, size)
                cells.append(c)
                print(f"{mechanism} b={bits} size=2^{int(math.log2(size))}: "
                      f"ratio={c['payload_ratio']:.3f} "
                      f"enc fused={c['encode_s']['fused_xla']*1e3:.2f}ms "
                      f"unfused={c['encode_s']['unfused']*1e3:.2f}ms "
                      f"ks={c['ks']['stat']:.4f}"
                      f"{'' if c['ks']['pass'] else ' (clamp-limited)'} "
                      f"dev={c['fused_vs_unfused_max_dev']:.2e}")

    acc = next(c for c in cells
               if (c["mechanism"], c["bits"], c["size"]) == ACCEPTANCE)
    assert acc["payload_ratio"] <= 0.5, acc
    assert acc["ks"]["pass"], acc
    print(f"acceptance {ACCEPTANCE}: payload_ratio="
          f"{acc['payload_ratio']:.3f} <= 0.5, KS pass")

    out = {
        "benchmark": "fused_compress",
        "clip": CLIP,
        "n_for_bits": {str(k): v for k, v in N_FOR_BITS.items()},
        "acceptance_cell": list(ACCEPTANCE),
        "cells": cells,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
