"""Operations and bytes from shapes, for the utilisation and roofline
metrics.  Everything here is a function of a configuration file's sizes
(``common.dense_sizes``) and a traffic file, never of the program.

Model FLOPs count what the forward and backward passes require: two per
multiply-add, the backward twice the forward, causal attention over the
(T + 1) / 2 positions a query sees on average.  Recomputation under remat
and the masked half of each attention block do not count.
"""
from __future__ import annotations

import math


def layer_leaf_shapes(s: dict) -> dict:
    """Parameter shapes of one decoder layer of the published block."""
    d, ff, hq, hk, hd = s["d"], s["ff"], s["hq"], s["hk"], s["hd"]
    out = {"wq": (d, hq * hd), "wk": (d, hk * hd), "wv": (d, hk * hd),
           "wo": (hq * hd, d), "norm1_w": (d,), "norm2_w": (d,)}
    if s["qkv_bias"]:
        out.update(bq=(hq * hd,), bk=(hk * hd,), bv=(hk * hd,))
    if s["act"] == "swiglu":
        out.update(w_gate=(d, ff), w_up=(d, ff), w_down=(ff, d))
    else:
        out.update(w_up=(d, ff), w_down=(ff, d))
    if s["mlp_bias"]:
        out.update(b_up=(ff,), b_down=(d,))
    if s["norm"] == "layernorm":
        out.update(norm1_b=(d,), norm2_b=(d,))
    return out


def layer_coords(s: dict) -> int:
    return sum(math.prod(v) for v in layer_leaf_shapes(s).values())


def layer_matmul_params(s: dict) -> int:
    return sum(math.prod(v) for v in layer_leaf_shapes(s).values()
               if len(v) == 2)


def model_params(s: dict) -> int:
    emb = s["vocab"] * s["d"] * (1 if s["tied"] else 2)
    final = s["d"] * (2 if s["norm"] == "layernorm" else 1)
    return s["layers"] * layer_coords(s) + emb + final


def forward_flops_per_token(s: dict, seq: int) -> float:
    """Matmuls of every layer and the output head, plus causal attention
    (scores and the weighted sum) at the mean context (seq + 1) / 2."""
    dense = s["layers"] * layer_matmul_params(s) + s["vocab"] * s["d"]
    attn = s["layers"] * 2 * 2 * s["hq"] * s["hd"] * (seq + 1) / 2
    return 2.0 * dense + attn


def train_flops_per_token(s: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(s, seq)


def fields_per_word(bits: int) -> int:
    return max(32 // int(bits), 1)


def codec_min_bytes(coords: int, bits: int) -> float:
    """Least HBM traffic of aggregating ``coords`` coordinates, whatever
    implements the draw: encode reads the f32 leaf and writes packed
    words, decode reads the summed words and writes the f32 aggregate.
    The dither and the shared randomness can be made where they are used,
    so they are not counted.  The same count bounds the fused kernels and
    a whole call."""
    word = 4.0 / fields_per_word(bits)
    return coords * (4.0 + 4.0 + 2.0 * word)
