"""Plain float32 reference for the dense decoder cells: weights from the
seed, the loss and its clipped gradient, and AdamW's first step.  It
imports nothing of the program.

The block is the published one (pre-norm decoder, RoPE with the halves
rotated as in hf transformers, grouped-query attention with query head h
reading key/value head h // (hq / hk), causal softmax, SwiGLU or tanh-GELU
MLP, tied output head, mean next-token NLL).  Departures, each also in
PERF.md:

* parameters are the program's set and names, so a bias the published
  block has and the program lacks (starcoder2's o_proj) is absent here;
* the codec's N(0, sigma^2) noise is the reference's own draw from the
  seed, not the program's (the codec cell checks the program's law);
* matmuls run at ``Precision.HIGHEST`` in float32, attention in blocks of
  query rows and the loss in blocks of tokens, each under remat, one row
  of the batch at a time, so that the whole fits on one chip.

``matmul="fp8"`` is the control: every matmul operand rounded to
float8_e4m3fn with a per-tensor scale, accumulated in float32.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
LOSS_BLOCK = 1024
FP8_MAX = 448.0


# ------------------------------------------------------------------ weights
def _leaf_init(key, name: str, shape):
    """Per-leaf rule by name: embeddings N(0, 0.02^2), matrices
    N(0, 1/fan_in), norm scales 1 + N(0, 0.02^2), biases N(0, 0.02^2)."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "embed":
        return 0.02 * z
    if name.startswith("w") or name == "lm_head":
        return z / jnp.sqrt(jnp.float32(shape[-2]))
    if name.endswith("_w") or name in ("q_norm", "k_norm"):
        return 1.0 + 0.02 * z
    return 0.02 * z


def init_params(key, shapes):
    """Fill a nested dict of shapes (``ShapeDtypeStruct`` leaves) with
    weights from ``key``; the leaf's path picks its key, its name its
    rule."""
    def rec(path, node):
        if isinstance(node, dict):
            return {k: rec(path + (k,), v) for k, v in node.items()}
        k = key
        for p in path:
            k = jax.random.fold_in(k, zlib.crc32(p.encode()) & 0x7FFFFFFF)
        return _leaf_init(k, path[-1], tuple(node.shape)).astype(node.dtype)

    return rec((), shapes)


# ------------------------------------------------------------------- matmul
def _fp8(x):
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = jax.lax.stop_gradient(scale)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    # straight-through: the rounding is in the forward and in the
    # operands the backward sees, the derivative is the identity
    return x + jax.lax.stop_gradient(q - x)


def _einsum(matmul: str):
    def f(spec, a, b):
        if matmul == "fp8":
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return f


# ------------------------------------------------------------------ forward
def _norm(s, x, w, b):
    if s["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + s["eps"]) * w + b
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + s["eps"]) * w


def _rope(s, T):
    hd = s["hd"]
    inv = 1.0 / (s["theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _apply_rope(x, cos, sin):  # x (B, T, H, D)
    c, sn = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _attention(s, ein, q, k, v):
    """Causal softmax attention in blocks of query rows.  q (B, T, hq, hd),
    k/v (B, T, hk, hd) -> (B, T, hq * hd)."""
    B, T = q.shape[:2]
    g = s["hq"] // s["hk"]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    qblk = min(Q_BLOCK, T)
    nb = T // qblk
    qb = q.reshape(B, nb, qblk, s["hq"], s["hd"]).swapaxes(0, 1)
    kpos = jnp.arange(T)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def block(args):
        i, qi = args
        sc = ein("bqhd,bkhd->bhqk", qi, k) / jnp.sqrt(jnp.float32(s["hd"]))
        qpos = i * qblk + jnp.arange(qblk)
        sc = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return ein("bhqk,bkhd->bqhd", p, v)

    o = jax.lax.map(block, (jnp.arange(nb), qb))  # (nb, B, Qb, hq, hd)
    return o.swapaxes(0, 1).reshape(B, T, s["hq"] * s["hd"])


def _layer(s, ein, rope, x, lp):
    a = lp["attn"]
    B, T, _ = x.shape
    h = _norm(s, x, lp["norm1_w"], lp.get("norm1_b"))

    def proj(w, b, heads):
        y = ein("btd,de->bte", h, a[w])
        if b in a:
            y = y + a[b]
        return y.reshape(B, T, heads, s["hd"])

    q = _apply_rope(proj("wq", "bq", s["hq"]), *rope)
    k = _apply_rope(proj("wk", "bk", s["hk"]), *rope)
    v = proj("wv", "bv", s["hk"])
    x = x + ein("bte,ed->btd", _attention(s, ein, q, k, v), a["wo"])
    h = _norm(s, x, lp["norm2_w"], lp.get("norm2_b"))
    m = lp["mlp"]
    if s["act"] == "swiglu":
        u = jax.nn.silu(ein("btd,df->btf", h, m["w_gate"])) * ein(
            "btd,df->btf", h, m["w_up"])
    else:
        u = jax.nn.gelu(ein("btd,df->btf", h, m["w_up"]) + m["b_up"],
                        approximate=True)
    y = ein("btf,fd->btd", u, m["w_down"])
    if "b_down" in m:
        y = y + m["b_down"]
    return x + y


def loss(s, params, tokens, matmul: str = "f32"):
    """Mean next-token NLL over the B x (T - 1) predicted positions."""
    ein = _einsum(matmul)
    B, T = tokens.shape
    x = params["embed"][tokens]
    rope = _rope(s, T)
    body = jax.checkpoint(lambda h, lp: (_layer(s, ein, rope, h, lp), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _norm(s, x, params["final_w"], params.get("final_b"))
    head = params["embed"] if s["tied"] else params["lm_head"].T
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    keep = jnp.broadcast_to(jnp.arange(T) < T - 1, (B, T)).astype(jnp.float32)
    lblk = min(LOSS_BLOCK, B * T)
    n = B * T // lblk
    hb = x.reshape(n, lblk, -1)
    lb = labels.reshape(n, lblk)
    kb = keep.reshape(n, lblk)

    @jax.checkpoint
    def block(args):
        h, lab, kp = args
        logits = ein("nd,vd->nv", h, head)
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, lab[:, None], -1)[:, 0]
        return jnp.sum(nll * kp)

    return jnp.sum(jax.lax.map(block, (hb, lb, kb))) / (B * (T - 1))


# ----------------------------------------------------------------- training
@functools.lru_cache(maxsize=None)
def _loss_and_grad(sizes: tuple, matmul: str):
    s = dict(sizes)

    def f(params, tokens, clip):
        l, g = jax.value_and_grad(lambda p: loss(s, p, tokens, matmul))(params)
        return l, jax.tree.map(lambda x: jnp.clip(x, -clip, clip), g)

    return jax.jit(f)


def loss_and_grad(s, params, tokens, matmul: str, clip: float):
    """Loss of ``tokens`` and its gradient, clipped per coordinate."""
    return _loss_and_grad(tuple(sorted(s.items())), matmul)(
        params, tokens, jnp.float32(clip))


@functools.partial(jax.jit, donate_argnums=0)
def add(a, b):
    return jax.tree.map(jnp.add, a, b)


@functools.partial(jax.jit, donate_argnums=0)
def scale(tree, a):
    return jax.tree.map(lambda x: x * a, tree)


@functools.partial(jax.jit, donate_argnums=0)
def with_noise(tree, a, sigma, key):
    """a * tree plus N(0, sigma^2) on every coordinate, drawn from key."""
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(treedef, [
        x * a + sigma * jax.random.normal(jax.random.fold_in(key, i),
                                          x.shape, jnp.float32)
        for i, x in enumerate(leaves)])


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps"))
def adamw_first_update(grad, *, lr, b1, b2, eps):
    """AdamW's change of the parameters at step 1 from zero moments, with
    no weight decay."""
    def upd(g):
        m, v = (1 - b1) * g, (1 - b2) * g * g
        return -lr * (m / (1 - b1)) / (jnp.sqrt(v / (1 - b2)) + eps)

    return jax.tree.map(upd, grad)
