"""Pallas TPU kernel: block-wise online-softmax (flash) attention.

Grid (BH, n_q, n_kv) with the KV axis innermost ("arbitrary" semantics):
running (max, sum, acc) statistics live in VMEM scratch across KV steps;
the output tile is written on the last KV block.  Fully-masked causal
blocks are skipped with ``pl.when`` — unlike the pure-JAX scan fallback
(repro.models.attention), the skipped upper-triangle work is actually
*not executed*, which is the main §Perf motivation for the kernel.

Validated in interpret mode against ref.mha_ref (tests/test_kernels.py);
the TARGET is TPU v5e (MXU-aligned 128-lane tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, bq: int, bk: int, kv_len: int):
    i_kv = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(i_kv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = pl.program_id(1) * bq
    k_start = i_kv * bk
    run = True
    if causal:
        run = k_start <= q_start + bq - 1  # skip fully-masked blocks

    @pl.when(run)
    def _step():
        q = q_ref[0, ...].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0, ...].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < kv_len  # padded keys
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = mask & (cols <= rows)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...][:, :1]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = l_scr[...][:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, ...].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(i_kv == n_kv - 1)
    def _finish():
        l = l_scr[...][:, :1]
        o_ref[0, ...] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_tpu(q, k, v, *, causal: bool = True, bq: int = 128,
                        bk: int = 128, kv_len: int | None = None,
                        interpret: bool = False):
    """q: (BH, T, D); k, v: (BH, S, D) -> (BH, T, D). T, S must be
    multiples of bq, bk (ops.py pads); ``kv_len`` masks padded keys."""
    BH, T, D = q.shape
    S = k.shape[1]
    bq = min(bq, T)
    bk = min(bk, S)
    grid = (BH, pl.cdiv(T, bq), pl.cdiv(S, bk))
    kern = functools.partial(
        _kernel, scale=D**-0.5, causal=causal, bq=bq, bk=bk,
        kv_len=S if kv_len is None else kv_len,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max
            pltpu.VMEM((bq, 128), jnp.float32),  # running sum
            pltpu.VMEM((bq, D), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
