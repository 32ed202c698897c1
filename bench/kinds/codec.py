"""Codec cells: the program's ``compress_tree`` jitted over a gradient tree
of real shapes, one call per round with a fresh round key.

Set-up makes the tree from the seed on the device (one jitted call) and
makes one warm-up call, which compiles.  The window calls the same
compiled program until the first call boundary after ``--seconds``, with
at least ``min_calls`` calls.  ``checked_calls`` of the window's outputs,
drawn from the seed by reservoir sampling over the calls, are kept.

Compared, after the window: ``ks_scaled`` is sqrt(N) times the
Kolmogorov-Smirnov distance between the N pooled errors (decoded output
minus the clipped input) of the kept calls and N(0, sigma^2), the
mechanism's exact law.  Scaled so, its law does not depend on N (the
Kolmogorov distribution: median 0.83, 99.9% quantile 1.95), so one limit
holds at every size.  It covers the shared (A, B) draw, the dither, both
fused kernels and the packing.
"""
from __future__ import annotations

import gc
import math
import random
import time

import jax
import jax.numpy as jnp

import common
import counts
import stats


def tree_shapes(ctx) -> dict:
    t = ctx.traffic
    if t["tree"] != "decoder_layer":
        raise ValueError(f"unknown gradient tree {t['tree']!r}")
    return counts.layer_leaf_shapes(common.dense_sizes(ctx.config))


def make_tree(key, shapes, values):
    if values["dist"] != "uniform":
        raise ValueError(f"unknown value law {values['dist']!r}")
    return {name: jax.random.uniform(common.name_key(key, name), shape,
                                     jnp.float32, values["low"],
                                     values["high"])
            for name, shape in shapes.items()}


def codec_config(c: dict, **override):
    from repro.dist.compress import CompressionConfig

    c = {**c, **override}
    return CompressionConfig(
        mechanism=c["mechanism"], sigma=c["sigma"], clip=c["clip"],
        per_coord=c["per_coord"], fused=c["fused"], msg_bits=c["msg_bits"])


@jax.jit
def _errors(outs, x, clip):
    """Flat f32 errors of a list of output trees against the clipped x."""
    return jnp.concatenate([
        (y[k] - jnp.clip(x[k], -clip, clip)).reshape(-1)
        for y in outs for k in sorted(x)])


class Cell:
    def __init__(self, ctx, **codec_override):
        self.ctx = ctx
        self.t = ctx.traffic
        if int(self.t["clients"]) != 1 or ctx.chips != 1:
            raise ValueError("codec cells run one client on one chip")
        self.shapes = tree_shapes(ctx)
        self.coords = sum(math.prod(s) for s in self.shapes.values())
        self.key = common.seed_key(ctx.seed)
        self.comp = codec_config(self.t["codec"], **codec_override)
        self.rng = random.Random(ctx.seed)

    def setup(self):
        from repro.dist.compress import compress_tree

        shapes, values = self.shapes, self.t["values"]
        self.x = jax.jit(lambda k: make_tree(k, shapes, values))(self.key)
        comp = self.comp
        self.fn = jax.jit(lambda v, k: compress_tree(v, comp, k))
        self.round = 0
        jax.block_until_ready(self.call())

    def call(self):
        k = jax.random.fold_in(jax.random.fold_in(self.key, 1), self.round)
        self.round += 1
        return self.fn(self.x, k)

    def window(self, seconds: float, span):
        keep_n = int(self.t["checked_calls"])
        min_calls = int(self.t["min_calls"])
        kept, calls, pending = [], 0, None
        t0 = time.perf_counter()
        while True:
            with span("bench.call"):
                y = self.call()
            calls += 1
            # reservoir sampling, drawn from the seed, over the calls
            if len(kept) < keep_n:
                kept.append(y)
            else:
                j = self.rng.randrange(calls)
                if j < keep_n:
                    kept[j] = y
            if pending is not None:
                with span("bench.wait"):
                    jax.block_until_ready(pending)
            pending = y
            del y
            if calls >= min_calls and time.perf_counter() - t0 >= seconds:
                break
        with span("bench.wait"):
            jax.block_until_ready(pending)
        elapsed = time.perf_counter() - t0
        self.kept = kept
        return {"attempted": calls, "failed": 0, "elapsed_s": elapsed,
                "calls": calls, "coords": calls * self.coords}

    def end_to_end(self, w):
        return {"codec_coords_per_s": common.metric(
            w["coords"] / w["elapsed_s"], "coords/s")}

    def free(self):
        self.fn = None
        gc.collect()

    def readings(self):
        err = _errors(self.kept, self.x, jnp.float32(self.comp.clip))
        self.kept = None
        finite = bool(jnp.all(jnp.isfinite(err)))
        ks = stats.ks_normal_device(err, self.comp.sigma) if finite else 1.0
        common.log(f"codec: {err.size} errors from {len(self.shapes)} "
                   f"leaves, KS {ks}, std/sigma "
                   f"{float(jnp.std(err)) / self.comp.sigma:.6f}")
        return {"ks_scaled": ks * math.sqrt(err.size)}

    def check(self):
        return self.readings()

